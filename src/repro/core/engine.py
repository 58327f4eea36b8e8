"""Batched multi-layer MSE engine: one jitted XLA program per model search.

The paper's DSE loop (Sec 2.4 / Fig 6) runs a full map-space exploration per
benchmark layer at *every* DSE step.  A per-layer Python GA dispatches one
``evaluate_population`` per layer per generation plus host-side numpy GA
operators — ``L x generations`` device round-trips.  This engine stacks the
GA state of all rows (a row = one (layer, spec) pair) into an ``(L, P, 10)``
genome tensor and moves decode, cost evaluation, selection, crossover and
mutation into a single ``jax.lax.fori_loop`` with a *traced* generation
count, so one model-level MSE is exactly one XLA dispatch.

Compile-once design (the whole fig7+fig13 suite shares one program):

  * rows are processed in fixed-size chunks (``ROW_BUCKET``); short chunks
    are padded with inert rows and large row sets are split, so any model /
    spec-set reuses the same compiled program;
  * O/P/S/R index tables are padded to the class-wide C_X maxima (720
    orders, 30 pairs, |FullFlex shapes|, R_PAD widths) and indexed modulo
    their *true* lengths, so InFlex / PartFlex / FullFlex specs all present
    identical shapes;
  * the hard-partition flag is a traced per-row input, not a static;
  * the generation count is a traced ``fori_loop`` bound; draw arrays are
    zero-padded to a ``GEN_BUCKET`` multiple (never executed past the
    bound).

Randomness is drawn host-side and shipped as scan inputs.  Each row reads
the numpy Generator seeded with the mapper's convention
(``cfg.seed + 1000 * first_occurrence_index``), whose calls depend on the
row's map space only through the R test (``ga_ops.r_open``).  So one
:func:`run_batched_ga` call draws each (seed, R-open) stream once
(``ga_ops.draw_stream``, kept in a :class:`StreamMemo` for the call) and
derives every row that reads it from that stream under the row's map space
(``ga_ops.row_draws``): a row's arrays equal ``initial_population`` then
``draw_run`` on its own Generator.  A fully device-side ``jax.random``
variant was measured and rejected: on the CPU backend the threefry key
derivation tripled both compile time and steady-state latency, and it
would change every stream (see docs/mapper.md).

Golden parity with the per-layer reference GA of tests/_reference_ga.py is
by construction: both consume the same per-row draw streams and apply the
same ``ga_ops`` operator arithmetic (float32 mutate steps, stable argsort,
strict-improve best tracking) — see tests/test_batched_engine.py.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from functools import partial
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.pool import InFlightQueue

from . import device_pool, ga_ops, tracing
from .cost_model import CostResult, evaluate_kinds_impl
from .ga_ops import GENOME_LEN, GenDraws
from .mapspace import mapspace_for, padded_tables
from .spec import FlexSpec, HWConfig
from .workloads import Layer, group_table

ROW_BUCKET = 64     # rows per program; larger row sets run in chunks
GEN_BUCKET = 16     # draw arrays padded to a multiple of this
TABLE_BUCKET = 8    # distinct spec table-sets per chunk, padded (shape-stable)
DRAW_WORKERS = 4    # most threads drawing one chunk's rows (see _draw_workers)


def _bucket(n: int, base: int) -> int:
    b = base
    while b < n:
        b *= 2
    return b


class RowResult(NamedTuple):
    """Host-side per-row outcome of a batched GA run."""

    best_genome: np.ndarray    # (10,) i32
    best_obj: float
    history: List[float]       # best objective per generation
    runtime: float
    energy: float
    edp: float
    util: float
    dram_elems: float
    feasible: bool


_GA_STATICS = ("hw", "n_elite", "objective", "with_repr")


@partial(jax.jit, static_argnames=_GA_STATICS)
def _ga_program(dims, stride, depthwise, tile_lo, tile_hi, hard_partition,
                table_id, orders, pairs, shapes, reprs, lens, pop0, draws,
                n_gens, grouped=None, *, hw: HWConfig, n_elite: int,
                objective: str, with_repr: bool = False):
    """The whole GA for all rows in one program.

    Shapes: dims (L,6) stride (L,) depthwise (L,) tile_lo/hi (L,6)
    hard_partition (L,) table_id (L,) orders (T,720,6) pairs (T,30,2)
    shapes (T,S,2) reprs (T,R_PAD) lens (T,4) pop0 (L,P,10) draws leaves
    (Gp,L,Pc,...) n_gens () traced; grouped (L,) or None when no row of
    the chunk is grouped (the program then holds no grouped term).

    ``with_repr`` (static) selects the cost-model program: False traces the
    pre-R graph (no width-scaling ops — XLA's FMA fusion then matches the
    v4 binaries bit-for-bit, the golden-parity discipline for native-pinned
    rows; ``reprs`` is dead code and DCE'd); True threads each mapping's
    decoded bit-width into the width-scaled cost model.
    """
    return _ga_run(dims, stride, depthwise, tile_lo, tile_hi, hard_partition,
                   table_id, orders, pairs, shapes, reprs, lens, pop0, draws,
                   n_gens, grouped, None, hw, n_elite, objective, with_repr)


@partial(jax.jit, static_argnames=_GA_STATICS)
def _ga_program_ragged(dims, stride, depthwise, tile_lo, tile_hi,
                       hard_partition, table_id, orders, pairs, shapes, reprs,
                       lens, pop0, draws, n_gens, grouped, group_dims,
                       group_live, *, hw: HWConfig, n_elite: int,
                       objective: str, with_repr: bool = False):
    """:func:`_ga_program`'s ragged variant: every row costs the sum over
    its ``group_dims`` (L, G, 6) nests where ``group_live`` (L, G), under
    the row's one mapping (docs/mapper.md "Layer kinds").  A program of its
    own, chosen per chunk like ``with_repr``: the engine packs ragged rows
    into chunks of their own, so a chunk with none never runs it."""
    return _ga_run(dims, stride, depthwise, tile_lo, tile_hi, hard_partition,
                   table_id, orders, pairs, shapes, reprs, lens, pop0, draws,
                   n_gens, grouped, (group_dims, group_live), hw, n_elite,
                   objective, with_repr)


def _ga_run(dims, stride, depthwise, tile_lo, tile_hi, hard_partition,
            table_id, orders, pairs, shapes, reprs, lens, pop0, draws, n_gens,
            grouped, groups, hw: HWConfig, n_elite: int, objective: str,
            with_repr: bool):
    """Body of both GA programs (traced inside their jit)."""
    n_rows, population, _ = pop0.shape
    row_lens = lens[table_id]                        # (L, 4)
    lo_b = tile_lo[:, None, :]
    hi_b = tile_hi[:, None, :]
    lens_b = row_lens[:, None, :]

    def decode(pop):
        oi = jnp.mod(pop[..., 6], row_lens[:, None, 0])
        pi = jnp.mod(pop[..., 7], row_lens[:, None, 1])
        si = jnp.mod(pop[..., 8], row_lens[:, None, 2])
        tid = table_id[:, None]
        if with_repr:
            ri = jnp.mod(pop[..., 9], row_lens[:, None, 3])
            bits = reprs[tid, ri]
        else:
            bits = None
        return (pop[..., 0:6], orders[tid, oi], pairs[tid, pi],
                shapes[tid, si], bits)

    def evaluate(pop) -> CostResult:
        tiles, order, par, shape_rc, bits = decode(pop)

        def per_row(d_, s_, w_, hp_, g_, gr_, t_, o_, p_, sh_, b_):
            def per_mapping(t1, o1, p1, s1, b1):
                return evaluate_kinds_impl(d_, s_, w_, t1, o1, p1, s1, hw,
                                           hp_, b1, g_, gr_)
            return jax.vmap(per_mapping)(t_, o_, p_, sh_, b_)

        return jax.vmap(per_row)(dims, stride, depthwise, hard_partition,
                                 grouped, groups, tiles, order, par,
                                 shape_rc, bits)

    def body(i, carry):
        pop, best_obj, best_g, best_res, hist = carry
        d = jax.tree_util.tree_map(lambda x: x[i], draws)
        with jax.named_scope("evaluate"):
            res = evaluate(pop)
        with jax.named_scope("select"):
            obj = getattr(res, objective)                      # (L, P)
            order_idx = jnp.argsort(obj, axis=1, stable=True)
            gen_best = order_idx[:, 0]
            gen_obj = jnp.take_along_axis(obj, gen_best[:, None],
                                          axis=1)[:, 0]
            improved = gen_obj < best_obj
            best_obj = jnp.where(improved, gen_obj, best_obj)
            gen_g = jnp.take_along_axis(pop, gen_best[:, None, None],
                                        axis=1)[:, 0]
            best_g = jnp.where(improved[:, None], gen_g, best_g)
            # carry the winner's full cost breakdown (cheaper than a second
            # evaluate instance after the loop)
            best_res = CostResult(*(
                jnp.where(improved,
                          jnp.take_along_axis(f, gen_best[:, None],
                                              axis=1)[:, 0],
                          bf)
                for f, bf in zip(res, best_res)))
            hist = hist.at[i].set(best_obj)

        with jax.named_scope("breed"):
            elites = jnp.take_along_axis(pop, order_idx[:, :n_elite, None],
                                         axis=1)
            parent_idx = jnp.take_along_axis(order_idx, d.ranks, axis=1)
            parents = jnp.take_along_axis(pop, parent_idx[..., None], axis=1)
            children = ga_ops.apply_crossover(parents, d, jnp)
            children = ga_ops.clip_genomes(children, lo_b, hi_b, lens_b, jnp)
            children = ga_ops.apply_mutation(children, d, lo_b, hi_b, lens_b,
                                             jnp)
            pop = jnp.concatenate([elites, children], axis=1)
        return pop, best_obj, best_g, best_res, hist

    gens_pad = draws.step.shape[0]
    zeros = jnp.zeros((n_rows,), jnp.float32)
    carry0 = (pop0,
              jnp.full((n_rows,), jnp.inf, jnp.float32),
              pop0[:, 0, :],
              CostResult(runtime=zeros, energy=zeros,
                         feasible=jnp.zeros((n_rows,), jnp.bool_),
                         util=zeros, dram_elems=zeros, l2_elems=zeros,
                         edp=zeros),
              jnp.full((gens_pad, n_rows), jnp.inf, jnp.float32))
    _, best_obj, best_g, best, hist = jax.lax.fori_loop(0, n_gens, body,
                                                        carry0)
    return best_g, best_obj, hist, best


@dataclasses.dataclass(frozen=True)
class EngineRow:
    """One (layer, spec, seed) search request; seeds follow the mapper's
    convention (``cfg.seed + 1000 * first_occurrence_index``)."""

    layer: Layer
    spec: FlexSpec
    seed: int


class ChunkInputs(NamedTuple):
    """Host-side arrays of one padded engine chunk, ready to dispatch."""

    dims: np.ndarray
    stride: np.ndarray
    depthwise: np.ndarray
    tile_lo: np.ndarray
    tile_hi: np.ndarray
    hard_partition: np.ndarray
    table_id: np.ndarray
    orders: np.ndarray
    pairs: np.ndarray
    shapes: np.ndarray
    reprs: np.ndarray
    lens: np.ndarray
    pop0: np.ndarray
    draws: GenDraws
    gens: int
    grouped: Optional[np.ndarray] = None     # (L,) when a row is grouped
    group_dims: Optional[np.ndarray] = None  # (L, G, 6) when a row is ragged
    group_live: Optional[np.ndarray] = None  # (L, G)


# GAConfig fields deliberately NOT folded into ga_params_key, with why each
# one can never change a row result.  The REP008 lint compares this dict +
# the key against the fields the dispatch path actually reads: adding a
# GAConfig field fails lint until it is classified here or keyed.
GA_KEY_EXCLUDED_FIELDS = {
    "pipeline": "scheduling only; per-chunk inputs/outputs unchanged",
    "devices": "placement only; sharded results are bit-identical",
    "seed": "keyed per-row: row_cache_key folds EngineRow.seed instead",
}


def ga_params_key(cfg) -> tuple:
    """The GAConfig fields a row's search RESULT depends on, as a hashable
    key.  Placement/scheduling knobs (``pipeline``, ``devices``)
    are deliberately absent — they never change results (the golden-parity
    contract) — and ``seed`` lives on each :class:`EngineRow`, not here.
    Two configs with equal keys produce bit-identical rows, which is what
    lets the DSE service share engine rows across clients with different
    GAConfig objects."""
    return ("ga-v1", cfg.population, cfg.generations, cfg.elite_frac,
            cfg.mutation_rate, cfg.crossover_rate, cfg.tile_divisor_bias,
            cfg.objective)


def row_cache_key(row: EngineRow, cfg) -> tuple:
    """Canonical persistent-cache key of one engine row: GA params + spec +
    the spec-relevant layer fields (dims, stride, kind, group rows) + the
    row seed.  Layer *names* are
    excluded (the ``mapper._dedup_key`` discipline), so equal shapes from
    different models/clients share one cached result."""
    layer = row.layer
    return ("mapper-row", ga_params_key(cfg), row.spec,
            tuple(int(d) for d in layer.dims), int(layer.stride),
            bool(layer.depthwise), layer.kind, layer.group_rows,
            int(row.seed))


def run_batched_ga(rows: Sequence[EngineRow], cfg,
                   row_cache=None) -> List[RowResult]:
    """Search all rows batched; returns per-row results in order (``[]`` for
    an empty row set — an empty campaign is a valid campaign).  All rows
    must share an HWConfig (one static ``hw`` per program).

    With ``row_cache`` (a :class:`repro.core.result_cache.ResultCache`),
    rows are answered from the cache when a bit-identical search — same
    :func:`row_cache_key` — was already run, and rows that share a key
    WITHIN this call (e.g. the same (layer, spec, seed) requested by two
    service clients) dispatch once.  Cached results are bit-identical to a
    fresh dispatch by the engine's parity contract, so the returned list is
    unchanged by any cache state; only the amount of device work varies.

    Row sets larger than ``ROW_BUCKET`` run in bucket-sized chunks so that
    *every* call — any model, any number of specs — reuses the same compiled
    program instead of forcing a bigger-shape recompile.  Ragged rows are
    packed into chunks of their own, after the others: only those chunks
    run the ragged program variant (``_ga_program_ragged``).  Rows are
    independent, so the packing changes no result.

    Rows that read the same Generator stream (:func:`_stream_key`: equal
    seed and R test) share its draws: the call's :class:`StreamMemo` holds
    each stream from the first chunk that reads it to the last, in host
    memory only, and is gone when the call returns.

    Chunks are independent, so they can run anywhere: with a device pool
    (``cfg.devices`` or ``REPRO_DEVICES``, see ``repro.core.device_pool``)
    chunk ``i`` is ``device_put`` onto pool device ``i % D`` and the same
    compiled program executes there.  Placement is the ONLY change, so
    sharded results are bit-identical to the single-device run.  Without
    ``cfg.pipeline`` the chunk loop stays synchronous — placement then just
    pins chunks (e.g. steering work off a busy default device); devices
    only crunch *concurrently* when the pipeline keeps chunks in flight.

    With ``cfg.pipeline`` the chunk loop is software-pipelined through an
    :class:`~repro.dist.pool.InFlightQueue`: chunk ``i`` is dispatched (JAX
    dispatch is asynchronous) and while the device crunches it, the host
    assembles the next chunks' draw streams — the host-side hot path of a
    campaign-sized row set, derived row by row on ``_prepare_chunk``'s draw
    threads from the call's :class:`StreamMemo` — keeping up to one chunk in
    flight *per pool device* before
    blocking on the oldest.  Scheduling only; per-chunk
    inputs and outputs are unchanged, so results stay bit-identical to the
    unpipelined loop.  If preparing or dispatching a later chunk raises, the
    already-dispatched in-flight chunks are still collected (never abandoned
    mid-device) and the error is re-raised with the failing chunk's context.
    """
    if not rows:
        return []
    if row_cache is not None:
        keys = [row_cache_key(r, cfg) for r in rows]
        cached = [row_cache.get(k) for k in keys]
        todo_rows: List[EngineRow] = []
        todo_keys: List[tuple] = []
        first_pos: dict = {}
        for r, k, c in zip(rows, keys, cached):
            if c is None and k not in first_pos:
                first_pos[k] = len(todo_rows)
                todo_rows.append(r)
                todo_keys.append(k)
        fresh = run_batched_ga(todo_rows, cfg)   # row_cache=None: dispatch
        # merge keeps the first stored result; nothing is cached if the
        # dispatch raised above, so a retry starts clean
        stored = {k: row_cache.merge(k, res)
                  for k, res in zip(todo_keys, fresh)}
        return [c if c is not None else stored[k]
                for k, c in zip(keys, cached)]
    hw = rows[0].spec.hw
    assert all(r.spec.hw == hw for r in rows), \
        "batched rows must share an HWConfig"
    pool = device_pool.pool_for(cfg)
    chunk_pos = pack_chunks([r.layer for r in rows])
    chunks = [[rows[i] for i in pos] for pos in chunk_pos]
    memo = StreamMemo(rows)
    out: List[RowResult] = []
    if getattr(cfg, "pipeline", False):
        n_chunks = len(chunks)

        def collect_with_context(idx, n_rows, gens, outputs):
            try:
                return _collect_chunk(n_rows, gens, outputs)
            except Exception as e:
                raise RuntimeError(
                    f"engine chunk {idx}/{n_chunks} failed during "
                    f"collection") from e

        queue = InFlightQueue(depth=len(pool) if pool else 1,
                              collect=collect_with_context)
        try:
            for idx, chunk in enumerate(chunks):
                try:
                    inputs = _prepare_chunk(chunk, cfg, hw, memo)
                    outputs = _dispatch_chunk(
                        inputs, cfg, hw,
                        device=pool.device_for(idx) if pool else None)
                except Exception as e:
                    raise RuntimeError(
                        f"engine chunk {idx}/{n_chunks} (rows "
                        f"{chunk_pos[idx][0]}..{chunk_pos[idx][-1]}"
                        f") failed during prepare/dispatch") from e
                out.extend(queue.push(idx, len(chunk), inputs.gens, outputs))
            out.extend(queue.drain())
        except Exception:
            # never abandon dispatched device work: block on every
            # remaining in-flight chunk (each drain attempt consumes at
            # least one entry, so this terminates) before propagating the
            # chunk-contextualized error
            while len(queue):
                try:
                    queue.drain()
                except Exception:  # noqa: BLE001 - original error wins
                    pass
            raise
    else:
        for idx, chunk in enumerate(chunks):
            inputs = _prepare_chunk(chunk, cfg, hw, memo)
            out.extend(_collect_chunk(
                len(chunk), inputs.gens,
                _dispatch_chunk(inputs, cfg, hw,
                                device=pool.device_for(idx) if pool
                                else None)))
    by_row: List[Optional[RowResult]] = [None] * len(rows)
    for i, res in zip((i for pos in chunk_pos for i in pos), out):
        by_row[i] = res
    return by_row


def pack_chunks(layers: Sequence[Layer]) -> List[List[int]]:
    """Positions of ``layers`` in ``ROW_BUCKET``-sized chunks: the
    non-ragged rows in order, then the ragged ones in chunks of their own,
    so only those run the ragged program variant."""
    parts = ([i for i, l in enumerate(layers) if not l.ragged],
             [i for i, l in enumerate(layers) if l.ragged])
    return [part[start:start + ROW_BUCKET] for part in parts
            for start in range(0, len(part), ROW_BUCKET)]


def _host_cores() -> int:
    """CPU cores this process may run on."""
    if hasattr(os, "sched_getaffinity"):
        return len(os.sched_getaffinity(0))
    return os.cpu_count() or 1


def _draw_workers(n_items: int) -> int:
    """Threads for ``n_items`` of a chunk's draws (its new streams, or its
    live rows): one per item, up to the host's cores and ``DRAW_WORKERS``.
    A single item is drawn inline on the calling thread."""
    return max(1, min(n_items, _host_cores(), DRAW_WORKERS))


_DRAW_POOL_LOCK = threading.Lock()
_draw_pool_executor: Optional[ThreadPoolExecutor] = None


def _draw_pool() -> ThreadPoolExecutor:
    """The process's draw threads, made on first use and kept for every
    later chunk.  numpy releases the GIL in the Generator's bulk fills and
    in its array loops, which is most of a row's draw time."""
    global _draw_pool_executor
    with _DRAW_POOL_LOCK:
        if _draw_pool_executor is None:
            _draw_pool_executor = ThreadPoolExecutor(
                max_workers=DRAW_WORKERS - 1,
                thread_name_prefix="engine-draws")
        return _draw_pool_executor


def _on_draw_threads(fn, n: int) -> int:
    """``fn(i)`` for each ``i < n`` on :func:`_draw_workers` threads: the
    caller and the draw pool's threads each take every k-th item.  Every
    thread finishes before this returns or an error leaves, so nothing
    is written after the chunk is abandoned.  Returns the thread count."""
    workers = _draw_workers(n)

    def run(first: int) -> None:
        for i in range(first, n, workers):
            fn(i)

    helpers = [_draw_pool().submit(run, w) for w in range(1, workers)]
    try:
        run(0)
    finally:
        wait(helpers)
    for h in helpers:
        h.result()
    return workers


def _stream_key(row: EngineRow) -> tuple:
    """The draw stream a row reads: its seed and its R test, which decides
    the R-axis draws (``ga_ops.r_open``); nothing else of the layer or spec
    touches the Generator."""
    return row.seed, ga_ops.r_open(padded_tables(row.spec).lens)


class StreamMemo:
    """The draw streams of one :func:`run_batched_ga` call by
    :func:`_stream_key`: each is drawn by the first chunk that needs it
    and dropped after its last row, counted from the call's rows.  A
    stream at GA 100 x 100 holds ~1.2 MB of host memory, plus the per-gene
    columns its rows derive (``ga_ops.RunStream.columns``, ~0.4 MB a
    stream in a fig13 sweep)."""

    def __init__(self, rows: Sequence[EngineRow]):
        self.uses = collections.Counter(_stream_key(r) for r in rows)
        self.streams: dict = {}

    def release(self, keys) -> None:
        for k in keys:
            self.uses[k] -= 1
            if self.uses[k] <= 0:
                self.streams.pop(k, None)


def _prepare_chunk(rows: Sequence[EngineRow], cfg, hw: HWConfig,
                   memo: Optional[StreamMemo] = None) -> ChunkInputs:
    """Assemble one chunk's padded host arrays (tables, populations, draw
    streams).  Pure host work — under ``cfg.pipeline`` it overlaps the
    previous chunk's device compute.

    Rows that share a :func:`_stream_key` share their Generator's draws
    (``ga_ops.draw_stream``): the streams ``memo`` lacks are drawn first,
    on :func:`_draw_workers` threads (a stream is a few long Generator
    fills, which release the interpreter lock), then each row's population
    and draws are derived from its stream under its map space
    (``ga_ops.row_draws``) on the calling thread: a row is ~30 short numpy
    copies, and handing the interpreter lock between threads for each
    cost more than the threads gained (PERF.md).  ``memo`` carries the
    streams across the chunks of one call; without it the chunk draws
    every stream it reads.  Each row's arrays equal its own Generator's,
    so the chunk is bit-identical to a one-Generator-per-row draw.  An
    error in any stream is raised here once every thread is done.

    Grouped rows add the traced ``grouped`` flags and ragged rows the
    group tables of the ragged program variant (``ChunkInputs``)."""
    memo = StreamMemo(rows) if memo is None else memo
    kinds = [row.layer.kind for row in rows]
    ragged = [row.layer for row in rows if row.layer.ragged]
    with tracing.span("engine.prepare", rows=len(rows), chunks=1,
                      grouped_rows=kinds.count("grouped"),
                      ragged_rows=len(ragged),
                      groups=sum(len(l.group_dims()) for l in ragged)):
        population = cfg.population
        n_children = population - ga_ops.n_elite(cfg)
        gens = cfg.generations
        gens_pad = _bucket(max(gens, 1), GEN_BUCKET)
        n_pad = ROW_BUCKET

        # -- distinct padded table sets + per-row table id ------------------
        # The table axis is padded to TABLE_BUCKET so that any number of
        # distinct specs (1..bucket) presents the same shapes — no recompile
        # per spec-set.
        with tracing.span("engine.prepare.tables"):
            spec_ids = {}
            tables = []
            table_id = np.zeros(n_pad, np.int32)
            for i, row in enumerate(rows):
                if row.spec not in spec_ids:
                    spec_ids[row.spec] = len(tables)
                    tables.append(padded_tables(row.spec))
                table_id[i] = spec_ids[row.spec]
            t_pad = _bucket(len(tables), TABLE_BUCKET)
            orders = np.zeros((t_pad,) + tables[0].orders.shape, np.int32)
            pairs = np.zeros((t_pad,) + tables[0].pairs.shape, np.int32)
            shapes = np.zeros((t_pad,) + tables[0].shapes.shape, np.int32)
            # inert table slots decode to the native width (bits index 0 via
            # lens=1)
            reprs = np.full((t_pad,) + tables[0].reprs.shape,
                            8 * hw.bytes_per_elem, np.int32)
            lens = np.ones((t_pad, 4), np.int32)
            for ti, t in enumerate(tables):
                orders[ti], pairs[ti], shapes[ti], reprs[ti], lens[ti] = (
                    t.orders, t.pairs, t.shapes, t.reprs, t.lens)

        # -- per-row state + draws, inert-padded to the buckets -------------
        with tracing.span("engine.prepare.draws") as draws_span:
            keys = [_stream_key(row) for row in rows]
            new = [k for k in dict.fromkeys(keys) if k not in memo.streams]
            drawn = [None] * len(new)

            def draw_stream(j: int) -> None:
                drawn[j] = ga_ops.draw_stream(*new[j], cfg, gens, n_children)

            workers = _on_draw_threads(draw_stream, len(new))
            memo.streams.update(zip(new, drawn))

            dims = np.ones((n_pad, 6), np.int32)
            stride = np.ones(n_pad, np.int32)
            depthwise = np.zeros(n_pad, np.bool_)
            tile_lo = np.ones((n_pad, 6), np.int32)
            tile_hi = np.ones((n_pad, 6), np.int32)
            hard_partition = np.zeros(n_pad, np.bool_)
            grouped = np.zeros(n_pad, np.bool_)
            pop0 = np.ones((n_pad, population, GENOME_LEN), np.int32)
            draw_stack = ga_ops.empty_draw_stack(gens_pad, n_pad, n_children,
                                                 gens, len(rows))
            for i, row in enumerate(rows):
                space = mapspace_for(row.layer, row.spec)
                pop0[i], _ = ga_ops.row_draws(
                    memo.streams[keys[i]], space,
                    GenDraws(*(f[:gens, i] for f in draw_stack)))
                dims[i] = space.dims
                stride[i] = row.layer.stride
                depthwise[i] = row.layer.depthwise
                grouped[i] = row.layer.grouped
                tile_lo[i] = space.tile_lo
                tile_hi[i] = space.tile_hi
                hard_partition[i] = space.hard_partition
            draws_span.counts.update(workers=workers, streams=len(new))
            memo.release(keys)

        group_dims = group_live = None
        if ragged:
            with tracing.span("engine.prepare.groups"):
                group_dims, group_live = group_table(
                    [row.layer for row in rows], n_pad)

    return ChunkInputs(dims=dims, stride=stride, depthwise=depthwise,
                       tile_lo=tile_lo, tile_hi=tile_hi,
                       hard_partition=hard_partition, table_id=table_id,
                       orders=orders, pairs=pairs, shapes=shapes,
                       reprs=reprs, lens=lens, pop0=pop0, draws=draw_stack,
                       gens=gens,
                       grouped=grouped if grouped.any() else None,
                       group_dims=group_dims, group_live=group_live)


def _dispatch_chunk(c: ChunkInputs, cfg, hw: HWConfig, device=None):
    """Launch the chunk's GA program; returns device arrays without blocking
    (JAX async dispatch), so the caller can overlap further host work.

    With ``device`` the chunk's arrays are committed there first, so the
    program executes on that device (jit follows committed inputs); the
    program and inputs are otherwise identical, hence identical outputs."""
    with tracing.span("engine.dispatch"):
        # native-pinned chunks run the pre-R program (bit parity with v4);
        # only a chunk with an open or off-native R table pays the scaled
        # graph
        native = 8 * hw.bytes_per_elem
        with_repr = any(
            int(l) > 1 or (r[:max(int(l), 1)] != native).any()
            for r, l in zip(c.reprs, c.lens[:, 3]))
        args = (c.dims, c.stride, c.depthwise, c.tile_lo, c.tile_hi,
                c.hard_partition, c.table_id, c.orders, c.pairs, c.shapes,
                c.reprs, c.lens, c.pop0, c.draws, np.int32(c.gens),
                c.grouped)
        # ragged chunks run their own program variant
        program = _ga_program
        if c.group_dims is not None:
            program = _ga_program_ragged
            args += (c.group_dims, c.group_live)
        if device is not None:
            args = jax.device_put(args, device)
        return program(
            *args, hw=hw, n_elite=ga_ops.n_elite(cfg),
            objective=cfg.objective, with_repr=with_repr)


def _collect_chunk(n_rows: int, gens: int, outputs) -> List[RowResult]:
    """Materialize a dispatched chunk (blocks on the device) and unpack the
    live rows."""
    with tracing.span("engine.wait"):
        jax.block_until_ready(outputs)
    with tracing.span("engine.unpack"):
        best_g, best_obj, hist, best = outputs
        best_g = np.asarray(best_g)
        best_obj = np.asarray(best_obj)
        hist = np.asarray(hist)
        best = CostResult(*(np.asarray(f) for f in best))
        out = []
        for i in range(n_rows):
            out.append(RowResult(
                best_genome=best_g[i],
                best_obj=float(best_obj[i]),
                history=[float(v) for v in hist[:gens, i]],
                runtime=float(best.runtime[i]),
                energy=float(best.energy[i]),
                edp=float(best.edp[i]),
                util=float(best.util[i]),
                dram_elems=float(best.dram_elems[i]),
                feasible=bool(best.feasible[i]),
            ))
    return out


def warmup_engine(cfg, hw: Optional[HWConfig] = None) -> None:
    """Trigger the (one-time) engine compile for a GA budget outside any
    timed region — e.g. before a benchmark loop.  With a device pool
    (``cfg.devices`` / ``REPRO_DEVICES``) the warmup chunk is dispatched to
    EVERY pool device, so per-device executables are ready before the timed
    chunks round-robin over them."""
    from .spec import make_variant
    hw = hw or HWConfig()
    row = EngineRow(Layer("warmup", (4, 4, 4, 4, 1, 1)),
                    make_variant("1111", hw=hw), seed=0)
    pool = device_pool.pool_for(cfg)
    inputs = _prepare_chunk([row], cfg, hw)
    for dev in (pool.devices if pool else (None,)):
        _collect_chunk(1, inputs.gens,
                       _dispatch_chunk(inputs, cfg, hw, device=dev))
