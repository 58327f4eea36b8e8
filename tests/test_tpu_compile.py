"""Compile the Pallas kernels and the engine's GA program for a described
TPU v5e, with no chip attached.

The TPU compiler is installed with jaxlib, and it compiles for a topology
that is only described: what it refuses here (a block shape off the
8/16/32 x 128 tile, more VMEM than a kernel may use, a dot Mosaic cannot
lower) it would refuse on the chip.  Interpret mode on the CPU checks none
of that.  Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module-scoped fixture and never while a
module is imported: only one process at a time may load the TPU library, and
every test worker imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.autotune_bench import SHAPES
from repro.core import (HWConfig, attention_workload, config_legal,
                        lower_mapping, make_variant, mamba_workload,
                        mapspace_for, matmul_workload)
from repro.core.kernel_bridge import (REAL_WIDTH, REAL_WIDTH_BLOCKS,
                                      VMEM_BUDGET_BYTES)
from repro.kernels import dtype_for_bits
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.tiled_matmul import tiled_matmul, vmem_bytes


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no libtpu log files
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_and_args(kind, shape, block, order, dtype, sharding):
    """The raw Pallas kernel (interpret off) and its argument shapes."""
    if kind == "matmul":
        m, n, k = shape
        bm, bn, bk = block
        fn = functools.partial(tiled_matmul, bm=bm, bn=bn, bk=bk,
                               order=order)
        return fn, (_sds((m, k), dtype, sharding),
                    _sds((k, n), dtype, sharding))
    if kind == "attention":
        h, s, d = shape
        bq, bkv = block
        fn = functools.partial(flash_attention, causal=True, bq=bq, bkv=bkv)
        return fn, tuple(_sds((h, s, d), dtype, sharding) for _ in range(3))
    b, length, d, n = shape
    chunk, d_block = block
    fn = functools.partial(mamba_scan, chunk=chunk, d_block=d_block)
    return fn, (_sds((b, length, d), dtype, sharding),
                _sds((b, length, d), dtype, sharding),
                _sds((b, length, n), dtype, sharding),
                _sds((b, length, n), dtype, sharding),
                _sds((d, n), jnp.float32, sharding),
                _sds((d,), jnp.float32, sharding))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int8],
                         ids=["bf16", "f32", "int8"])
@pytest.mark.parametrize("order", ["out", "a", "b"])
def test_matmul_compiles_at_real_width(one_chip, order, dtype):
    block = REAL_WIDTH_BLOCKS["matmul"][order]
    m, n, _ = REAL_WIDTH["matmul"]
    assert vmem_bytes(*block, jnp.dtype(dtype).itemsize, order, m, n) \
        <= VMEM_BUDGET_BYTES
    fn, args = _kernel_and_args("matmul", REAL_WIDTH["matmul"], block,
                                order, dtype, one_chip)
    hlo = _compile(fn, *args)
    assert "tpu_custom_call" in hlo
    out = jax.eval_shape(fn, *args)
    assert out.shape == (m, n)
    assert out.dtype == (jnp.int32 if dtype == jnp.int8 else dtype)


def test_attention_compiles_at_real_width(one_chip):
    fn, args = _kernel_and_args("attention", REAL_WIDTH["attention"],
                                REAL_WIDTH_BLOCKS["attention"], "",
                                jnp.bfloat16, one_chip)
    assert "tpu_custom_call" in _compile(fn, *args)


def test_mamba_scan_compiles_at_real_width(one_chip):
    fn, args = _kernel_and_args("mamba", REAL_WIDTH["mamba"],
                                REAL_WIDTH_BLOCKS["mamba"], "", jnp.float32,
                                one_chip)
    assert "tpu_custom_call" in _compile(fn, *args)


_WORKLOADS = {
    "matmul": lambda s: matmul_workload(*s),
    "attention": lambda s: attention_workload(*s),
    "mamba": lambda s: mamba_workload(*s),
}


@pytest.mark.parametrize("spec_class", ["11001", "1100"])
@pytest.mark.parametrize("kind", ["matmul", "attention", "mamba"])
def test_every_lowered_config_compiles(one_chip, kind, spec_class):
    """Every config ``lower_mapping`` emits for a seeded genome sample at
    the autotune bench's ``full`` shapes compiles for the chip: the R-open
    spec reaches the int8 and bf16 paths, the f32 spec is the bench's."""
    spec = (make_variant(spec_class, hw=HWConfig()) if spec_class == "11001"
            else make_variant(spec_class, hw=HWConfig(), fixed_bits=32))
    wl = _WORKLOADS[kind](SHAPES["full"][kind])
    space = mapspace_for(wl.layer, spec)
    genomes = space.clip(space.sample(np.random.default_rng(0), 24))
    configs = {lower_mapping(wl, space.decode(g)) for g in genomes}
    assert configs
    for cfg in sorted(configs, key=repr):
        assert config_legal(wl, cfg), cfg
        dtype = dtype_for_bits(cfg.bits, kind)
        fn, args = _kernel_and_args(kind, wl.shape, cfg.block, cfg.order,
                                    dtype, one_chip)
        assert "tpu_custom_call" in _compile(fn, *args), cfg


@pytest.mark.parametrize("with_repr", [False, True])
def test_ga_program_compiles(one_chip, with_repr):
    """The engine's GA program for one chunk of mnasnet rows, at a small
    population: the program is the paper-budget one at other shapes (the
    generation count is a traced argument), and compiles in seconds where
    population 100 takes about 25."""
    from repro.core import GAConfig, get_model
    from repro.core import engine, ga_ops
    from repro.core.mapper import plan_model_rows, request_rows

    cfg, hw = GAConfig(population=8, generations=2), HWConfig()
    layers = get_model("mnasnet")[:4]
    row_index, _ = plan_model_rows(layers)
    rows = request_rows(layers, make_variant("11111", hw=hw), cfg, row_index)
    c = engine._prepare_chunk(rows, cfg, hw)
    args = jax.tree_util.tree_map(
        lambda a: _sds(np.shape(a), np.asarray(a).dtype, one_chip),
        (c.dims, c.stride, c.depthwise, c.tile_lo, c.tile_hi,
         c.hard_partition, c.table_id, c.orders, c.pairs, c.shapes, c.reprs,
         c.lens, c.pop0, c.draws, np.int32(c.gens)))
    compiled = engine._ga_program.lower(
        *args, hw=hw, n_elite=ga_ops.n_elite(cfg), objective=cfg.objective,
        with_repr=with_repr).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


@pytest.mark.parametrize("ragged", [False, True])
def test_ga_program_kinds_compile(one_chip, ragged):
    """The GA program of a chunk of Kimi-K2 decode rows: grouped rows (the
    traced grouped flags) or ragged rows (the ragged program variant, its
    group tables), at a small population."""
    from repro.core import GAConfig, get_model
    from repro.core import engine, ga_ops
    from repro.core.mapper import plan_model_rows, request_rows

    cfg, hw = GAConfig(population=8, generations=2), HWConfig()
    layers = [l for l in get_model("kimi-k2-decode32k") if l.ragged == ragged]
    row_index, _ = plan_model_rows(layers)
    rows = request_rows(layers, make_variant("1111", hw=hw), cfg, row_index)
    c = engine._prepare_chunk(rows, cfg, hw)
    assert c.grouped is not None
    assert (c.group_dims is not None) == ragged
    program = engine._ga_program_ragged if ragged else engine._ga_program
    tail = (c.grouped, c.group_dims, c.group_live) if ragged else (c.grouped,)
    args = jax.tree_util.tree_map(
        lambda a: _sds(np.shape(a), np.asarray(a).dtype, one_chip),
        (c.dims, c.stride, c.depthwise, c.tile_lo, c.tile_hi,
         c.hard_partition, c.table_id, c.orders, c.pairs, c.shapes, c.reprs,
         c.lens, c.pop0, c.draws, np.int32(c.gens)) + tail)
    compiled = program.lower(
        *args, hw=hw, n_elite=ga_ops.n_elite(cfg), objective=cfg.objective,
        with_repr=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30
