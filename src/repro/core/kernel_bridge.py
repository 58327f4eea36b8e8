"""Genome -> Pallas kernel lowering: the model-to-measurement bridge.

The mapper ranks 10-gene ``Mapping`` genomes with the analytical cost model;
this module makes those genomes *executable*.  It lowers a mapping onto the
knobs the real kernels expose, checks the lowered config against the same
legality the cost model enforces, and closes the loop with a
measured-runtime objective the GA can optimize directly:

  T genes  -> ``tiled_matmul`` block shapes ``(bm, bn, bk)``,
              ``flash_attention`` tiles ``(bq, bkv)``,
              ``mamba_scan`` chunking ``(chunk, d_block)``
  O gene   -> ``tiled_matmul`` stationarity order ("out" / "a" / "b")
  R gene   -> executed kernel dtype via ``kernels.kernel_bits`` and the
              width-aware ``vmem_bytes`` helpers (``precision.bytes_of``)

Lowering is TOTAL and deterministic: every genome the cost model can rate —
feasible or not — snaps to a legal config (``_snap_block`` always finds a
divisor, and ``lower_mapping`` shrinks blocks until the VMEM budget holds),
so no cost-model-feasible mapping can fail to lower.  The buffer-side
legality the mapper applies (``raw_tile_feasibility``) is mirrored here in
numpy (``bridge_tile_feasible``) with the identical float32 arithmetic, and
the property tests pin the two to exact agreement.

``MeasuredRunner`` times lowered kernels (compiled on TPU, interpret mode on
CPU) behind a ``ResultCache`` timing cache, and ``tune_kernel`` walks the
reference GA's trajectory with measured wall-clock as the objective —
falling back to the modeled objective when Pallas is unavailable
(``REPRO_NO_PALLAS=1``), so the tier-1 suite stays hermetic.
``rank_correlation_study`` records how well the model's predicted cost
ranks real measured cost per mapping (the ``benchmarks.run --autotune``
BENCH pass).

``core -> kernels`` is a one-way dependency: kernel modules are imported
lazily inside the functions that execute or size them, so importing
``repro.core`` never pulls in Pallas.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from .. import kernels as _k
from . import ga_ops
from .envvars import get_env
from .mapper import GAConfig
from .mapspace import Mapping, MapSpace, mapspace_for
from .precision import bytes_of
from .result_cache import ResultCache
from .spec import FlexSpec
from .workloads import Layer, gemm

# Mosaic's block rule: a block dimension that lands in the last two axes of
# a BlockSpec is a multiple of the TPU tile — LANES on the last axis,
# SUBLANES[executed bits] on the second-to-last — or the full array
# dimension.  The full dimension always meets the rule, so lowering always
# finds a block; a dim with no 128-multiple divisor leaves only the full dim,
# which may overflow VMEM, so ``config_legal`` still judges every lowering.
LANES = 128
SUBLANES = {32: 8, 16: 16, 8: 32}

# Per-core VMEM budget the lowered working set must fit (pallas guide).
VMEM_BUDGET_BYTES = 16 * 2 ** 20

BIG = 1e30


# --------------------------------------------------------------------------
# Workloads: the kernel-side view of a layer
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelWorkload:
    """One executable kernel instance plus its cost-model Layer twin.

    ``shape`` is kind-specific: matmul ``(m, n, k)``; attention
    ``(heads, seq, head_dim)`` (the score GEMM is the mapped layer); mamba
    ``(batch, seq, d_inner, d_state)``.
    """

    kind: str                    # "matmul" | "attention" | "mamba"
    shape: Tuple[int, ...]

    @property
    def layer(self) -> Layer:
        """The GEMM-normalized Layer the mapper searches: matmul
        (K=M, C=Kred, Y=N); attention scores (K=Sq, C=d, Y=Skv); mamba
        (K=D, C=N, Y=L)."""
        if self.kind == "matmul":
            m, n, k = self.shape
            return gemm(f"mm_{m}x{n}x{k}", m, n, k)
        if self.kind == "attention":
            h, s, d = self.shape
            return gemm(f"attn_h{h}_s{s}_d{d}", s, s, d)
        if self.kind == "mamba":
            b, length, d, n = self.shape
            return gemm(f"mamba_b{b}_l{length}_d{d}_n{n}", d, length, n)
        raise ValueError(f"unknown kernel kind {self.kind!r}")


def matmul_workload(m: int, n: int, k: int) -> KernelWorkload:
    return KernelWorkload("matmul", (m, n, k))


def workload_for_layer(layer: Layer) -> KernelWorkload:
    """The matmul kernel of a plain GEMM layer ``(M, Kg, N, 1, 1, 1)``.
    Any other layer is refused: a grouped or ragged GEMM lowered as one
    plain GEMM would share one weight operand across its groups (and a
    ragged one would pad every group to the largest), which is not the
    layer the cost model ranked; convolutions have no kernel here."""
    k, c, y, x, r, s = layer.dims
    if layer.kind in ("grouped", "ragged"):
        raise ValueError(
            f"layer {layer.name!r} is {layer.kind}: its groups each have "
            f"their own weights, and no kernel here lowers a {layer.kind} "
            f"GEMM (lowering it as one plain GEMM would share them)")
    if layer.kind != "plain" or (x, r, s) != (1, 1, 1):
        raise ValueError(f"layer {layer.name!r} is not a plain GEMM "
                         f"(dims {layer.dims}, {layer.kind})")
    return matmul_workload(k, y, c)


def attention_workload(heads: int, seq: int, head_dim: int
                       ) -> KernelWorkload:
    return KernelWorkload("attention", (heads, seq, head_dim))


def mamba_workload(batch: int, seq: int, d_inner: int, d_state: int
                   ) -> KernelWorkload:
    return KernelWorkload("mamba", (batch, seq, d_inner, d_state))


# The kernels at the widths of a real layer: one 4096-square GEMM, 16 heads
# of 4096-token attention at head_dim 128, and a 2048-channel selective scan
# over 4096 steps, with a legal block for each (matmul per order: the A/B-
# stationary orders keep a 4096-long output stripe resident, so their
# stripe-side block is halved to fit VMEM).
REAL_WIDTH = {"matmul": (4096, 4096, 4096), "attention": (16, 4096, 128),
              "mamba": (1, 4096, 2048, 16)}
REAL_WIDTH_BLOCKS = {
    "matmul": {"out": (512, 512, 512), "a": (256, 512, 512),
               "b": (512, 256, 512)},
    "attention": (256, 256),
    "mamba": (128, 512),
}


# --------------------------------------------------------------------------
# Lowering
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """A fully lowered, executable kernel configuration."""

    kind: str
    block: Tuple[int, ...]       # matmul (bm, bn, bk); attention (bq, bkv);
                                 # mamba (chunk, d_block)
    order: str                   # matmul stationarity; "" for other kinds
    bits: int                    # executed operand width (kernel_bits)

    def cache_key(self, wl: KernelWorkload) -> tuple:
        return ("kernel-timing", self.kind, wl.shape, self.block,
                self.order, self.bits)


def _snap_block(dim: int, target: int, align: int) -> int:
    """The block for ``dim`` nearest ``target`` under the block rule: the
    largest divisor of ``dim`` that is <= ``target`` and a multiple of
    ``align`` (or ``dim`` itself); when none is that small, the smallest
    such divisor.  Total: ``dim`` always qualifies."""
    dim = int(dim)
    legal = [int(d) for d in ga_ops.divisors(dim)
             if d % align == 0 or d == dim]
    below = [d for d in legal if d <= int(target)]
    return below[-1] if below else legal[0]


def _matmul_order(order_perm: Tuple[int, ...]) -> str:
    """O gene -> stationarity: the innermost of the GEMM dims K(=M-dim 0),
    C(=reduction dim 1), Y(=N-dim 2) in the loop order decides which operand
    stays resident (matches the tiled_matmul docstring semantics)."""
    pos = {d: i for i, d in enumerate(order_perm)}
    innermost = max((0, 1, 2), key=lambda d: pos[d])
    return {1: "out", 2: "a", 0: "b"}[innermost]


def _vmem(wl: KernelWorkload, cfg: "KernelConfig") -> float:
    """Width-aware VMEM working set of a lowered config (lazy kernel module
    imports keep repro.core Pallas-free)."""
    db = bytes_of(cfg.bits)
    if wl.kind == "matmul":
        from ..kernels.tiled_matmul import vmem_bytes
        m, n, _ = wl.shape
        return vmem_bytes(*cfg.block, db, cfg.order, m, n)
    if wl.kind == "attention":
        from ..kernels.flash_attention import vmem_bytes
        return vmem_bytes(*cfg.block, wl.shape[2], db)
    from ..kernels.mamba_scan import vmem_bytes
    return vmem_bytes(*cfg.block, wl.shape[3], db)


def _block_dims(wl: KernelWorkload) -> Tuple[int, ...]:
    """The workload dim each block component must divide."""
    if wl.kind == "matmul":
        m, n, k = wl.shape
        return (m, n, k)
    if wl.kind == "attention":
        return (wl.shape[1], wl.shape[1])
    return (wl.shape[1], wl.shape[2])         # (L, D)


def _block_aligns(kind: str, bits: int) -> Tuple[int, ...]:
    """The tile each block component must be a multiple of (unless it is
    the full dim), from where it lands in the kernel's BlockSpecs: matmul
    bm is the sublane axis of the A block, bn and bk are lane axes (of the
    B block and the A block); attention bq and bkv are the sublane axes of
    the (1, b, d) Q and K/V blocks; the scan's chunk is a sublane axis and
    d_block the lane axis of the (1, chunk, d_block) x/dt/y blocks."""
    sub = SUBLANES[bits]
    if kind == "matmul":
        return (sub, LANES, LANES)
    if kind == "attention":
        return (sub, sub)
    return (sub, LANES)


def lower_mapping(wl: KernelWorkload, mapping: Mapping) -> KernelConfig:
    """Lower one Mapping onto the workload's kernel knobs.

    T genes are read through the same GEMM normalization the Layer uses
    (gene 0 = K-dim tile, 1 = C/reduction, 2 = Y-dim), snapped to divisors
    that follow the TPU block rule (``_block_aligns``); blocks then shrink
    (largest first) until the VMEM budget holds.  The result always meets
    the block rule, and is ``config_legal`` unless even the smallest legal
    blocks overflow VMEM.
    """
    t = mapping.tiles
    if wl.kind == "matmul":
        block = [t[0], t[2], t[1]]
        order = _matmul_order(mapping.order)
    elif wl.kind == "attention":
        block = [t[0], t[2]]
        order = ""
    elif wl.kind == "mamba":
        block = [t[2], t[0]]
        order = ""
    else:
        raise ValueError(f"unknown kernel kind {wl.kind!r}")
    bits = _k.kernel_bits(int(mapping.repr_bits), wl.kind)

    dims = _block_dims(wl)
    aligns = _block_aligns(wl.kind, bits)
    block = [_snap_block(d, b, a) for d, b, a in zip(dims, block, aligns)]
    cfg = KernelConfig(kind=wl.kind, block=tuple(block), order=order,
                       bits=bits)
    # shrink the largest block that can still shrink until VMEM fits
    while _vmem(wl, cfg) > VMEM_BUDGET_BYTES:
        smaller = [_snap_block(d, b // 2, a)
                   for d, b, a in zip(dims, cfg.block, aligns)]
        shrinkable = [i for i in range(len(block))
                      if smaller[i] < cfg.block[i]]
        if not shrinkable:
            break
        i = max(shrinkable, key=lambda j: cfg.block[j])
        cfg = dataclasses.replace(
            cfg, block=cfg.block[:i] + (smaller[i],) + cfg.block[i + 1:])
    return cfg


def lower_genome(wl: KernelWorkload, space: MapSpace,
                 genome: np.ndarray) -> KernelConfig:
    return lower_mapping(wl, space.decode(np.asarray(genome)))


def config_legal(wl: KernelWorkload, cfg: KernelConfig) -> bool:
    """The lowered-config legality predicate: each block divides its dim
    and follows the TPU block rule (a multiple of its tile from
    ``_block_aligns``, or the full dim), the width-aware VMEM budget holds,
    and the order and width are in the kernel's menus."""
    dims = _block_dims(wl)
    if len(cfg.block) != len(dims):
        return False
    if cfg.bits not in _k.SUPPORTED_BITS[cfg.kind]:
        return False
    for dim, b, a in zip(dims, cfg.block, _block_aligns(cfg.kind, cfg.bits)):
        if b < 1 or dim % b != 0 or (b % a != 0 and b != dim):
            return False
    if cfg.kind == "matmul" and cfg.order not in ("out", "a", "b"):
        return False
    return _vmem(wl, cfg) <= VMEM_BUDGET_BYTES


def bridge_tile_feasible(tiles: np.ndarray,
                         buffer_elems: float) -> np.ndarray:
    """Numpy mirror of ``mapper.raw_tile_feasibility`` — the SAME float32
    volume arithmetic, term for term, so the bridge and the cost model can
    never disagree about which raw tile genes fit the buffer (property-
    tested for exact equality).  tiles: (..., 6); returns (...,) bool."""
    t = np.asarray(tiles, np.float32)
    in_vol = t[..., 1] * (t[..., 2] - 1 + t[..., 4]) * \
        (t[..., 3] - 1 + t[..., 5])
    w_vol = t[..., 0] * t[..., 1] * t[..., 4] * t[..., 5]
    o_vol = t[..., 0] * t[..., 2] * t[..., 3]
    return (in_vol + w_vol + o_vol) <= np.float32(buffer_elems)


# --------------------------------------------------------------------------
# Predicted cost of a lowered config (the model side of the correlation)
# --------------------------------------------------------------------------

def effective_tiles(wl: KernelWorkload, cfg: KernelConfig
                    ) -> Tuple[int, ...]:
    """The T genes the kernel *actually* executes (lowered blocks mapped
    back through the GEMM normalization)."""
    if wl.kind == "matmul":
        bm, bn, bk = cfg.block
        return (bm, bk, bn, 1, 1, 1)
    if wl.kind == "attention":
        bq, bkv = cfg.block
        return (bq, wl.shape[2], bkv, 1, 1, 1)
    chunk, d_block = cfg.block
    return (d_block, wl.shape[3], chunk, 1, 1, 1)


def predicted_runtime(wl: KernelWorkload, spec: FlexSpec,
                      mapping: Mapping,
                      cfg: Optional[KernelConfig] = None) -> float:
    """Modeled runtime (cycles) of the mapping AS LOWERED: tiles snapped to
    the executed blocks, repr snapped to the executed width — the honest
    model-side number to correlate against a measurement."""
    import jax.numpy as jnp

    from .cost_model import evaluate_mapping

    cfg = cfg or lower_mapping(wl, mapping)
    layer = wl.layer
    res = evaluate_mapping(
        jnp.asarray(layer.dims), jnp.asarray(layer.stride),
        jnp.asarray(layer.depthwise),
        jnp.asarray(effective_tiles(wl, cfg), jnp.int32),
        jnp.asarray(mapping.order, jnp.int32),
        jnp.asarray(mapping.parallel, jnp.int32),
        jnp.asarray(mapping.shape, jnp.int32),
        spec.hw, mapspace_for(layer, spec).hard_partition,
        jnp.float32(cfg.bits))
    return float(res.runtime)


# --------------------------------------------------------------------------
# Measurement
# --------------------------------------------------------------------------

def make_inputs(wl: KernelWorkload, seed: int = 0) -> tuple:
    """Deterministic float32 input tensors for a workload.  Matmul inputs
    are integer-valued in {-1, 0, 1} so the int8-executed R widths cast
    losslessly and parity against the oracle is exact."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    if wl.kind == "matmul":
        m, n, k = wl.shape
        x = rng.integers(-1, 2, (m, k)).astype(np.float32)
        y = rng.integers(-1, 2, (k, n)).astype(np.float32)
        return (jnp.asarray(x), jnp.asarray(y))
    if wl.kind == "attention":
        h, s, d = wl.shape
        q, k, v = (rng.normal(size=(h, s, d)).astype(np.float32) * 0.5
                   for _ in range(3))
        return tuple(jnp.asarray(a) for a in (q, k, v))
    b, length, d, n = wl.shape
    x = rng.normal(size=(b, length, d)).astype(np.float32) * 0.5
    dt = rng.uniform(0.001, 0.1, (b, length, d)).astype(np.float32)
    bb = rng.normal(size=(b, length, n)).astype(np.float32) * 0.5
    cc = rng.normal(size=(b, length, n)).astype(np.float32) * 0.5
    a_log_neg = -rng.uniform(0.5, 2.0, (d, n)).astype(np.float32)
    d_skip = np.ones((d,), np.float32)
    return tuple(jnp.asarray(a)
                 for a in (x, dt, bb, cc, a_log_neg, d_skip))


def run_config(wl: KernelWorkload, cfg: KernelConfig, inputs: tuple,
               use_pallas: bool = True):
    """Execute one lowered config (compiled on TPU, interpreted on CPU —
    see ``kernels.ops``)."""
    from ..kernels import ops

    if wl.kind == "matmul":
        x, y = inputs
        bm, bn, bk = cfg.block
        return ops.matmul(x, y, bm=bm, bn=bn, bk=bk, order=cfg.order,
                          bits=cfg.bits, use_pallas=use_pallas)
    if wl.kind == "attention":
        q, k, v = inputs
        bq, bkv = cfg.block
        return ops.attention(q, k, v, causal=True, bq=bq, bkv=bkv,
                             bits=cfg.bits, use_pallas=use_pallas)
    chunk, d_block = cfg.block
    return ops.mamba_scan(*inputs, chunk=chunk, d_block=d_block,
                          bits=cfg.bits, use_pallas=use_pallas)


def reference_output(wl: KernelWorkload, cfg: KernelConfig, inputs: tuple):
    """The oracle's answer on the SAME width-cast operands the kernel sees
    (kernels/ref.py, pure jnp)."""
    from ..kernels import dtype_for_bits, ref

    dt = dtype_for_bits(cfg.bits, wl.kind)
    if wl.kind == "matmul":
        x, y = (a.astype(dt) for a in inputs)
        return ref.matmul_ref(x, y)
    if wl.kind == "attention":
        q, k, v = (a.astype(dt) for a in inputs)
        return ref.attention_ref(q, k, v, causal=True)
    x, dtt, b, c, a_log_neg, d_skip = inputs
    return ref.mamba_scan_ref(x.astype(dt), dtt.astype(dt), b.astype(dt),
                              c.astype(dt), a_log_neg, d_skip)


# (rtol, atol) per executed width — int8 paths are exact on the integer-
# valued matmul inputs; bf16 tolerances follow tests/test_kernels.py.
PARITY_TOLS = {8: (0.0, 0.0), 16: (2e-2, 0.16), 32: (2e-4, 2e-4)}


def parity_check(wl: KernelWorkload, cfg: KernelConfig,
                 inputs: Optional[tuple] = None) -> Tuple[bool, float]:
    """Golden-model check: lowered kernel vs kernels/ref oracle within the
    executed width's tolerance.  Returns (ok, max_abs_err)."""
    inputs = inputs if inputs is not None else make_inputs(wl)
    got = np.asarray(run_config(wl, cfg, inputs), np.float32)
    want = np.asarray(reference_output(wl, cfg, inputs), np.float32)
    rtol, atol = PARITY_TOLS[cfg.bits]
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    ok = bool(np.allclose(got, want, rtol=rtol, atol=atol))
    return ok, err


class MeasuredRunner:
    """Times lowered kernels behind a ResultCache timing cache.

    ``timer`` injects a fake measurement (key -> seconds) for hermetic,
    bit-reproducible tests; without it, real wall-clock is taken as the
    best of ``repeats`` timed calls after ``warmup`` compile/warm calls.
    ``force_available`` pins availability for tests; otherwise Pallas
    execution is unavailable only when ``REPRO_NO_PALLAS`` is set.
    """

    def __init__(self, cache: Optional[ResultCache] = None,
                 timer: Optional[Callable[[tuple], float]] = None,
                 repeats: int = 3, warmup: int = 1, input_seed: int = 0,
                 force_available: Optional[bool] = None):
        self.cache = cache if cache is not None else ResultCache()
        self.timer = timer
        self.repeats = max(1, int(repeats))
        self.warmup = max(0, int(warmup))
        self.input_seed = input_seed
        self.force_available = force_available
        self._inputs: Dict[KernelWorkload, tuple] = {}
        self.measured_calls = 0     # real/fake timings taken (cache misses)

    def available(self) -> bool:
        """False only on request (``force_available`` or the
        ``REPRO_NO_PALLAS`` opt-out); a Pallas install that fails to import
        raises instead of silently disabling measurement."""
        if self.force_available is not None:
            return bool(self.force_available)
        if get_env("REPRO_NO_PALLAS"):
            return False
        from ..kernels import ops  # noqa: F401
        return True

    def inputs_for(self, wl: KernelWorkload) -> tuple:
        if wl not in self._inputs:
            self._inputs[wl] = make_inputs(wl, self.input_seed)
        return self._inputs[wl]

    def _time(self, wl: KernelWorkload, cfg: KernelConfig) -> float:
        import jax

        inputs = self.inputs_for(wl)

        def call():
            return jax.block_until_ready(run_config(wl, cfg, inputs))

        for _ in range(self.warmup):
            call()
        best = np.inf
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            call()
            best = min(best, time.perf_counter() - t0)
        return float(best)

    def measure(self, wl: KernelWorkload, cfg: KernelConfig) -> float:
        """Seconds for one call of the lowered config (cached per config)."""
        key = cfg.cache_key(wl)
        hit = self.cache.get(key)
        if hit is not None:
            return float(hit)
        self.measured_calls += 1
        t = (float(self.timer(key)) if self.timer is not None
             else self._time(wl, cfg))
        return float(self.cache.merge(key, t))


# --------------------------------------------------------------------------
# Measured-objective GA tuning
# --------------------------------------------------------------------------

class TuneResult(NamedTuple):
    config: KernelConfig
    mapping: Mapping
    genome: np.ndarray
    objective: str               # "measured" | "modeled"
    best_cost: float             # seconds (measured) or cycles (modeled)
    predicted: float             # modeled runtime of the winner, as lowered
    history: Tuple[float, ...]   # best objective per generation
    measured_configs: int        # distinct configs actually timed


# Small default budget: measured tuning pays a jit compile per DISTINCT
# lowered config, so the sweet spot is few generations over a population
# that dedups heavily through the timing cache.
TUNE_CFG = GAConfig(population=12, generations=6)


def tune_kernel(wl: KernelWorkload, spec: FlexSpec,
                cfg: Optional[GAConfig] = None,
                runner: Optional[MeasuredRunner] = None) -> TuneResult:
    """GA search over the map space with MEASURED kernel wall-clock as the
    objective (modeled runtime when Pallas is unavailable).

    Walks the reference GA's exact trajectory — same seeded draw stream,
    same ``ga_ops.next_population`` breeding step — with the per-genome
    objective swapped: cost-model-feasible genomes are lowered and timed
    (deduped through the runner's timing cache), infeasible ones keep the
    model's BIG-penalized runtime and genomes whose lowering fails
    ``config_legal`` score BIG, so neither can win.  With a frozen
    timing cache (injected ``timer``) the whole trajectory is
    bit-reproducible.
    """
    import jax.numpy as jnp

    from .cost_model import evaluate_population

    cfg = cfg or TUNE_CFG
    runner = runner if runner is not None else MeasuredRunner()
    measured = runner.available()

    layer = wl.layer
    space = mapspace_for(layer, spec)
    rng = np.random.default_rng(cfg.seed)
    pop = ga_ops.initial_population(rng, space, cfg)
    n_elite = ga_ops.n_elite(cfg)
    draws = ga_ops.draw_run(rng, space, cfg, cfg.generations,
                            cfg.population - n_elite)
    lens = space.table_lens()

    dims = jnp.asarray(layer.dims)
    stride = jnp.asarray(layer.stride)
    dw = jnp.asarray(layer.depthwise)
    r_live = (len(space.repr_table) > 1
              or int(space.repr_table[0]) != 8 * spec.hw.bytes_per_elem)

    history: List[float] = []
    best_obj = np.inf
    best_g: Optional[np.ndarray] = None

    for gen in range(cfg.generations):
        tiles, orders, pairs, shapes, reprs = space.decode_batch(pop)
        res = evaluate_population(
            dims, stride, dw, jnp.asarray(tiles), jnp.asarray(orders),
            jnp.asarray(pairs), jnp.asarray(shapes), spec.hw,
            space.hard_partition,
            jnp.asarray(reprs) if r_live else None)
        modeled = np.asarray(res.runtime, np.float64)
        feasible = np.asarray(res.feasible)
        if measured:
            obj = modeled.copy()     # infeasible keep the BIG penalty
            for i in np.nonzero(feasible)[0]:
                kcfg = lower_genome(wl, space, pop[i])
                # a lowering the chip would refuse is never run
                obj[i] = (runner.measure(wl, kcfg)
                          if config_legal(wl, kcfg) else BIG)
        else:
            obj = modeled
        order_idx = np.argsort(obj, kind="stable")
        if obj[order_idx[0]] < best_obj:
            best_obj = float(obj[order_idx[0]])
            best_g = pop[order_idx[0]].copy()
        history.append(best_obj)

        pop = ga_ops.next_population(pop, order_idx,
                                     ga_ops.gen_slice(draws, gen),
                                     space.tile_lo, space.tile_hi, lens,
                                     n_elite, np)

    assert best_g is not None
    mapping = space.decode(best_g)
    kcfg = lower_mapping(wl, mapping)
    return TuneResult(
        config=kcfg, mapping=mapping, genome=best_g,
        objective="measured" if measured else "modeled",
        best_cost=best_obj,
        predicted=predicted_runtime(wl, spec, mapping, kcfg),
        history=tuple(history),
        measured_configs=len(runner.cache) if measured else 0,
    )


# --------------------------------------------------------------------------
# Predicted-vs-measured rank correlation (the --autotune BENCH metric)
# --------------------------------------------------------------------------

def _avg_ranks(v: np.ndarray) -> np.ndarray:
    """Average ranks with tie sharing (no scipy in the container)."""
    v = np.asarray(v, np.float64)
    order = np.argsort(v, kind="stable")
    ranks = np.empty(len(v), np.float64)
    i = 0
    sv = v[order]
    while i < len(sv):
        j = i
        while j + 1 < len(sv) and sv[j + 1] == sv[i]:
            j += 1
        ranks[order[i:j + 1]] = (i + j) / 2.0
        i = j + 1
    return ranks


def spearman(a, b) -> float:
    """Spearman rank correlation (average-rank Pearson); 0.0 when either
    side is constant."""
    ra, rb = _avg_ranks(a), _avg_ranks(b)
    ra = ra - ra.mean()
    rb = rb - rb.mean()
    denom = float(np.sqrt((ra * ra).sum() * (rb * rb).sum()))
    if denom == 0.0:
        return 0.0
    return float((ra * rb).sum() / denom)


def rank_correlation_study(wl: KernelWorkload, spec: FlexSpec,
                           n_samples: int = 16, seed: int = 0,
                           runner: Optional[MeasuredRunner] = None) -> dict:
    """Sample genomes, lower them, and correlate model-predicted runtime
    with measured wall-clock per DISTINCT lowered config.  Configs that
    fail ``config_legal`` are left out and never run; ``all_legal`` says
    whether any was.

    The sampled genome set, the lowered config set and the predicted costs
    are fully deterministic (seeded sampling + pure lowering); only the
    measured seconds are machine-dependent — BENCH gates the correlation's
    sign and the deterministic counts, and keeps the raw numbers as "_"
    sidecars.
    """
    runner = runner if runner is not None else MeasuredRunner()
    space = mapspace_for(wl.layer, spec)
    rng = np.random.default_rng(seed)
    genomes = space.clip(space.sample(rng, n_samples))

    configs: List[KernelConfig] = []
    predicted: List[float] = []
    seen = set()
    for g in genomes:
        mapping = space.decode(g)
        kcfg = lower_mapping(wl, mapping)
        if kcfg in seen:
            continue
        seen.add(kcfg)
        if config_legal(wl, kcfg):       # the chip would refuse the others
            configs.append(kcfg)
            predicted.append(predicted_runtime(wl, spec, mapping, kcfg))

    measured = [runner.measure(wl, kcfg) for kcfg in configs]
    corr = spearman(predicted, measured) if len(configs) >= 2 else 0.0
    legal = len(configs) == len(seen)
    return {
        "kind": wl.kind,
        "n_sampled": int(n_samples),
        "n_configs": len(configs),
        "all_legal": legal,
        "spearman": float(corr),
        "configs": configs,
        "predicted": predicted,
        "measured": measured,
    }
