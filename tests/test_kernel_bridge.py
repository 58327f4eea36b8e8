"""Genome -> Pallas lowering bridge: golden-model parity, legality/totality,
cost-model consistency, and measured-objective tuning determinism.

The tinyML-style discipline from ROADMAP: every lowered config executes in
interpret mode and is checked against the pure-jnp oracle; the bridge's
legality must agree exactly with the cost model's buffer feasibility; and
the measured-runtime GA must be bit-reproducible under a frozen timing
cache (fake timer) so tier-1 stays hermetic on CPU.
"""
import numpy as np
import pytest

from repro.core import (GAConfig, HWConfig, MeasuredRunner, ResultCache,
                        attention_workload, bridge_tile_feasible,
                        config_legal, lower_mapping, make_variant,
                        mamba_workload, mapspace_for, matmul_workload,
                        parity_check, raw_tile_feasibility, spearman,
                        tune_kernel)
from repro.core.kernel_bridge import (LANES, SUBLANES, _matmul_order,
                                      _snap_block, make_inputs)

from hypothesis import given, settings
from hypothesis import strategies as st

HW = HWConfig()
# T/O/R open, P/S pinned: the axes the kernels realize
SPEC5 = make_variant("11001", hw=HW)
# T/O open at fixed f32 (the autotune-bench spec)
SPEC_F32 = make_variant("1100", hw=HW, fixed_bits=32)

WORKLOADS = {
    "matmul": matmul_workload(64, 64, 64),
    "attention": attention_workload(2, 64, 32),
    "mamba": mamba_workload(1, 32, 16, 8),
}


def _sampled_mappings(wl, spec, n, seed=0):
    space = mapspace_for(wl.layer, spec)
    rng = np.random.default_rng(seed)
    return space, [space.decode(g) for g in space.clip(space.sample(rng, n))]


# -- golden-model parity sweep (satellite 1) -------------------------------

@pytest.mark.parametrize("kind", ["matmul", "attention", "mamba"])
def test_lowered_configs_match_oracle(kind):
    """Every lowered config for a genome sweep executes in interpret mode
    within the executed width's tolerance of kernels/ref.py."""
    wl = WORKLOADS[kind]
    _, mappings = _sampled_mappings(wl, SPEC5, 8, seed=1)
    inputs = make_inputs(wl)
    seen = set()
    for m in mappings:
        cfg = lower_mapping(wl, m)
        if cfg in seen:
            continue
        seen.add(cfg)
        ok, err = parity_check(wl, cfg, inputs)
        assert ok, f"{cfg} parity failed (max err {err})"
    assert seen, "sweep produced no configs"


def test_r_gene_selects_kernel_dtype():
    """The R gene reaches the executed dtype: sub-byte widths run int8 on
    matmul, 16 runs bfloat16, 32 runs float32; attention floors at bf16 and
    the scan at f32."""
    wl = WORKLOADS["matmul"]
    space = mapspace_for(wl.layer, SPEC5)
    base = space.decode(space.clip(space.sample(
        np.random.default_rng(0), 1))[0])
    import dataclasses
    for bits, want in ((2, 8), (4, 8), (8, 8), (16, 16), (32, 32)):
        cfg = lower_mapping(wl, dataclasses.replace(base, repr_bits=bits))
        assert cfg.bits == want
    att = lower_mapping(WORKLOADS["attention"],
                        dataclasses.replace(base, repr_bits=4))
    assert att.bits == 16
    scan = lower_mapping(WORKLOADS["mamba"],
                         dataclasses.replace(base, repr_bits=4))
    assert scan.bits == 32


# -- legality, totality, determinism (satellite 2) -------------------------

@pytest.mark.parametrize("kind", ["matmul", "attention", "mamba"])
def test_every_genome_lowers_to_legal_config(kind):
    """Totality: ANY clipped genome — feasible or not under the cost model —
    lowers to a config satisfying divisibility + VMEM + order legality."""
    wl = WORKLOADS[kind]
    _, mappings = _sampled_mappings(wl, SPEC5, 32, seed=2)
    for m in mappings:
        cfg = lower_mapping(wl, m)
        assert config_legal(wl, cfg), (m, cfg)


# Where each lowered block lands in the kernels' BlockSpecs, written out
# from kernels/*.py: (block index, array dim, axis) per spec, axis -1 the
# lane axis and -2 the sublane axis.
_SPEC_AXES = {
    "matmul": [(0, 0, -2), (2, 2, -1),      # A block (bm, bk)
               (2, 2, -2), (1, 1, -1),      # B block (bk, bn)
               (0, 0, -2), (1, 1, -1)],     # output (bm, bn) / stripes
    "attention": [(0, 0, -2), (1, 1, -2)],  # (1, bq, d), (1, bkv, d)
    "mamba": [(0, 0, -2), (1, 1, -1),       # (1, chunk, d_block)
              (1, 1, -2)],                  # A block (d_block, N)
}

BLOCK_RULE_WORKLOADS = [
    matmul_workload(512, 512, 256), matmul_workload(64, 192, 96),
    attention_workload(4, 512, 64), attention_workload(2, 96, 32),
    mamba_workload(2, 256, 128, 16), mamba_workload(1, 48, 200, 8),
]


@pytest.mark.parametrize("wl", BLOCK_RULE_WORKLOADS,
                         ids=lambda w: f"{w.kind}{w.shape}")
def test_lowered_configs_meet_block_rule(wl):
    """Host-only: every lowered block that lands in the last two axes of a
    BlockSpec is a multiple of the executed dtype's TPU tile (8/16/32
    sublanes by 128 lanes) or the full array dim, and the config is
    legal."""
    for spec in (SPEC5, SPEC_F32):
        _, mappings = _sampled_mappings(wl, spec, 48, seed=4)
        dims = {"matmul": lambda s: (s[0], s[1], s[2]),
                "attention": lambda s: (s[1], s[1]),
                "mamba": lambda s: (s[1], s[2])}[wl.kind](wl.shape)
        for m in mappings:
            cfg = lower_mapping(wl, m)
            assert config_legal(wl, cfg), (m, cfg)
            for bi, di, axis in _SPEC_AXES[wl.kind]:
                tile = LANES if axis == -1 else SUBLANES[cfg.bits]
                b, d = cfg.block[bi], dims[di]
                assert d % b == 0 and (b % tile == 0 or b == d), \
                    (wl, cfg, bi, axis)


def test_lowering_deterministic():
    wl = WORKLOADS["matmul"]
    _, mappings = _sampled_mappings(wl, SPEC5, 16, seed=3)
    for m in mappings:
        assert lower_mapping(wl, m) == lower_mapping(wl, m)


def test_snap_block_fixpoint_and_alignment():
    """_snap_block is total, divides, is idempotent (the lowering's
    fixpoint), returns an aligned divisor or the full dim, and stays at or
    under the target whenever a legal block that small exists."""
    for align in (8, 16, 32, LANES):
        for dim in (1, 3, 8, 24, 64, 96, 100, 128, 257, 512):
            for target in (1, 2, 5, 7, 8, 9, 63, 64, 1000):
                b = _snap_block(dim, target, align)
                assert 1 <= b <= dim and dim % b == 0
                assert b % align == 0 or b == dim
                assert _snap_block(dim, b, align) == b
                legal = [d for d in range(1, dim + 1) if dim % d == 0
                         and (d % align == 0 or d == dim)]
                if any(d <= target for d in legal):
                    assert b == max(d for d in legal if d <= target)
                else:
                    assert b == min(legal)
    assert _snap_block(128, 100, 8) == 64       # aligned divisor <= target
    assert _snap_block(96, 3, 8) == 8           # none <= 3: smallest aligned
    assert _snap_block(100, 64, LANES) == 100   # no lane multiple: full dim
    assert _snap_block(512, 200, LANES) == 128


def test_matmul_order_gene_semantics():
    """Innermost GEMM dim decides stationarity: C (reduction) innermost ->
    output-stationary, Y (N) innermost -> A-stationary, K (M) innermost ->
    B-stationary."""
    assert _matmul_order((3, 4, 5, 0, 2, 1)) == "out"
    assert _matmul_order((3, 4, 5, 0, 1, 2)) == "a"
    assert _matmul_order((3, 4, 5, 1, 2, 0)) == "b"


def test_bridge_feasibility_matches_cost_model_regression():
    """Pinned regression: the bridge's numpy buffer-feasibility mirror
    agrees EXACTLY with mapper.raw_tile_feasibility on random raw tiles
    (including points straddling the boundary)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    # 1..64 per dim straddles the 100K-element budget (volumes ~1e2..1e6)
    tiles = rng.integers(1, 64, (512, 6)).astype(np.int32)
    buf = float(HW.buffer_elems)
    want = np.asarray(raw_tile_feasibility(jnp.asarray(tiles), buf))
    got = bridge_tile_feasible(tiles, buf)
    assert np.array_equal(got, want)
    assert want.any() and (~want).any(), "sweep must straddle the boundary"


@given(st.lists(st.integers(min_value=1, max_value=512), min_size=6,
                max_size=6))
@settings(max_examples=60, deadline=None)
def test_bridge_feasibility_matches_cost_model_property(tiles):
    import jax.numpy as jnp
    t = np.asarray([tiles], np.int32)
    buf = float(HW.buffer_elems)
    want = np.asarray(raw_tile_feasibility(jnp.asarray(t), buf))
    assert np.array_equal(bridge_tile_feasible(t, buf), want)


@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_any_feasible_genome_lowers_legal_property(seed):
    """Property: a genome the cost model calls buffer-feasible always lowers
    to a legal kernel config, deterministically."""
    wl = WORKLOADS["matmul"]
    space = mapspace_for(wl.layer, SPEC5)
    g = space.clip(space.sample(np.random.default_rng(seed), 1))[0]
    m = space.decode(g)
    cfg = lower_mapping(wl, m)
    assert config_legal(wl, cfg)
    assert cfg == lower_mapping(wl, m)


# -- measured-objective tuning (satellite 4) -------------------------------

def _fake_timer(key):
    """Deterministic pseudo-measurement: a pure (process-independent) hash
    of the config key."""
    import zlib
    h = zlib.crc32(repr(key).encode()) % 10_000
    return 1e-4 + h * 1e-7


TUNE_CFG = GAConfig(population=8, generations=3)


def test_tune_kernel_frozen_timer_bit_reproducible():
    wl = WORKLOADS["matmul"]
    results = []
    for _ in range(2):
        runner = MeasuredRunner(cache=ResultCache(), timer=_fake_timer,
                                force_available=True)
        results.append(tune_kernel(wl, SPEC_F32, TUNE_CFG, runner))
    a, b = results
    assert a.objective == b.objective == "measured"
    assert a.config == b.config
    assert np.array_equal(a.genome, b.genome)
    assert a.history == b.history
    assert a.best_cost == b.best_cost
    assert a.predicted == b.predicted
    assert config_legal(wl, a.config)


def test_tune_kernel_timing_cache_dedups():
    """Repeat configs across generations hit the ResultCache: the fake
    timer is consulted once per distinct config."""
    calls = []

    def timer(key):
        calls.append(key)
        return _fake_timer(key)

    runner = MeasuredRunner(cache=ResultCache(), timer=timer,
                            force_available=True)
    res = tune_kernel(WORKLOADS["matmul"], SPEC_F32, TUNE_CFG, runner)
    assert len(calls) == len(set(calls)) == res.measured_configs > 0


def test_tune_kernel_modeled_fallback():
    """Pallas unavailable -> the tuner ranks by the modeled objective and
    still returns a legal lowered config, deterministically."""
    wl = WORKLOADS["attention"]
    runs = [tune_kernel(wl, SPEC_F32, TUNE_CFG,
                        MeasuredRunner(force_available=False))
            for _ in range(2)]
    a, b = runs
    assert a.objective == "modeled"
    assert a.measured_configs == 0
    assert a.config == b.config and a.history == b.history
    assert config_legal(wl, a.config)


def test_tune_kernel_env_kill_switch(monkeypatch):
    monkeypatch.setenv("REPRO_NO_PALLAS", "1")
    assert not MeasuredRunner().available()


def test_lowering_past_vmem_is_never_measured():
    """4100 has no divisor that is a multiple of 8 or 128, so the block rule
    leaves only full-dim blocks, and a 4100-square matmul overflows VMEM:
    every lowering is illegal, and neither the study nor the tuner hands
    one to the runner (on a chip it would crash Mosaic)."""
    from repro.core import rank_correlation_study
    from repro.core.kernel_bridge import BIG
    wl = matmul_workload(4100, 4100, 4100)
    calls = []

    def timer(key):
        calls.append(key)
        return _fake_timer(key)

    runner = MeasuredRunner(cache=ResultCache(), timer=timer,
                            force_available=True)
    study = rank_correlation_study(wl, SPEC_F32, n_samples=8, runner=runner)
    assert study["n_configs"] == 0 and not study["all_legal"]
    res = tune_kernel(wl, SPEC_F32, TUNE_CFG, runner)
    assert res.config.block == (4100, 4100, 4100)
    assert not config_legal(wl, res.config)
    assert res.best_cost >= BIG
    assert calls == []


@pytest.mark.parametrize("kind", ["matmul", "attention", "mamba"])
def test_tune_kernel_measured_end_to_end(kind):
    """Acceptance: a GA search with REAL measured wall-clock (interpret
    mode) runs end-to-end on CPU for each kernel kind and returns a legal
    config."""
    wl = WORKLOADS[kind]
    runner = MeasuredRunner(repeats=1, warmup=1)
    if not runner.available():
        pytest.skip("pallas unavailable")
    res = tune_kernel(wl, SPEC_F32,
                      GAConfig(population=6, generations=2),
                      runner)
    assert res.objective == "measured"
    assert res.best_cost > 0.0
    assert res.measured_configs > 0
    assert config_legal(wl, res.config)


def test_spearman_helper():
    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == 1.0
    assert spearman([1, 2, 3, 4], [4, 3, 2, 1]) == -1.0
    assert spearman([1, 1, 1], [1, 2, 3]) == 0.0
    assert abs(spearman([1, 2, 3, 4], [1, 2, 4, 3])) < 1.0
