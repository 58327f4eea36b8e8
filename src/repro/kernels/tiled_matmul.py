"""TOPS-configurable tiled matmul Pallas kernel (TPU target).

The paper's four flexibility axes, concretely, at the kernel level:

  T — block shape (bm, bn, bk): the VMEM tile sizes.  Legality = blocks fit
      VMEM and follow the TPU block rule (the analogue of "tiles fit the L2
      buffer").
  O — grid iteration order == which operand is *stationary* in VMEM:
        'out' : grid (M, N, K), K innermost — output-stationary, one
                (bm, bn) accumulator tile per output block
        'a'   : grid (M, K, N), N innermost — A-tile stationary; the
                partial sums of the whole (bm, N) output stripe stay in
                VMEM across the reduction loop
        'b'   : grid (N, K, M), M innermost — B-tile stationary; the
                (M, bn) output stripe stays in VMEM
  P — the grid itself (which dims are expanded spatially over cores).
  S — chosen one level up (mesh shape), see repro.core.tops_bridge.

A TPU kernel never reads an output block back from HBM, so every output
block is visited in one consecutive run of grid steps: the A/B-stationary
orders keep their output stripe resident instead of revisiting (bm, bn)
tiles.

Integer operands accumulate in int32 and return int32; floating operands
accumulate in float32 and return the operand dtype.

The flexibility-aware mapper (repro.core) picks (T, O) for a given GEMM
shape; `ops.matmul` is the jit entry point and `ref.matmul_ref` the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def acc_dtype(dtype) -> jnp.dtype:
    """Accumulator dtype for an operand dtype: int32 for integers, float32
    otherwise."""
    return jnp.dtype(jnp.int32 if jnp.issubdtype(dtype, jnp.integer)
                     else jnp.float32)


def out_dtype(dtype) -> jnp.dtype:
    """Result dtype: integer products stay in the int32 accumulator (an int8
    result would overflow); floats return the operand dtype."""
    return acc_dtype(dtype) if jnp.issubdtype(dtype, jnp.integer) \
        else jnp.dtype(dtype)


def _dot(x, y, acc):
    """Block product in the accumulator dtype; float32 operands take
    full-precision MXU passes (one bf16 pass would round them)."""
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    return jnp.dot(x, y, precision=precision, preferred_element_type=acc)


def _out_stationary_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(x_ref[...], y_ref[...], acc_ref.dtype)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _stripe_kernel(x_ref, y_ref, o_ref, *scratch, stripe_axis: int,
                   block: int):
    """A/B-stationary orders: grid (outer, kk, inner).  The output block is
    the whole stripe along the inner grid axis, resident for every step of
    one outer index; step (kk, inner) adds its product into the stripe
    slice ``inner`` (rows for 'b', columns for 'a')."""
    acc_ref = scratch[0] if scratch else o_ref
    kk, inner = pl.program_id(1), pl.program_id(2)

    @pl.when((kk == 0) & (inner == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = _dot(x_ref[...], y_ref[...], acc_ref.dtype)
    off = pl.multiple_of(inner * block, block)
    if stripe_axis == 1:
        acc_ref[:, pl.ds(off, block)] += part
    else:
        acc_ref[pl.ds(off, block), :] += part

    if scratch:
        @pl.when((kk == pl.num_programs(1) - 1)
                 & (inner == pl.num_programs(2) - 1))
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tiled_matmul(x: jnp.ndarray, y: jnp.ndarray, *,
                 bm: int = 128, bn: int = 128, bk: int = 128,
                 order: str = "out", interpret=False) -> jnp.ndarray:
    """x: (M, K) @ y: (K, N) -> (M, N) with explicit T (blocks) and O (order)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"blocks must divide dims: {(m, n, k)} vs {(bm, bn, bk)}"
    gm, gn, gk = m // bm, n // bn, k // bk
    acc = acc_dtype(x.dtype)
    out = jax.ShapeDtypeStruct((m, n), out_dtype(x.dtype))

    if order == "out":
        # grid (i, j, kk): K innermost; accumulator tile in VMEM scratch
        return pl.pallas_call(
            functools.partial(_out_stationary_kernel, n_k=gk),
            grid=(gm, gn, gk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=out,
            scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
            interpret=interpret,
        )(x, y)
    if order == "a":
        # grid (i, kk, j): N innermost; A block (i, kk) stationary across j
        grid = (gm, gk, gn)
        in_specs = [pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
                    pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j))]
        out_block, out_map = (bm, n), (lambda i, kk, j: (i, 0))
        kernel = functools.partial(_stripe_kernel, stripe_axis=1, block=bn)
    elif order == "b":
        # grid (j, kk, i): M innermost; B block (kk, j) stationary across i
        grid = (gn, gk, gm)
        in_specs = [pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk)),
                    pl.BlockSpec((bk, bn), lambda j, kk, i: (kk, j))]
        out_block, out_map = (m, bn), (lambda j, kk, i: (0, j))
        kernel = functools.partial(_stripe_kernel, stripe_axis=0, block=bm)
    else:
        raise ValueError(f"unknown order {order!r}")
    # accumulate in the resident output stripe when it already has the
    # accumulator dtype; narrower outputs get an accumulator stripe
    scratch = ([] if out.dtype == acc else [pltpu.VMEM(out_block, acc)])
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, out_map),
        out_shape=out, scratch_shapes=scratch, interpret=interpret,
    )(x, y)


def vmem_bytes(bm: int, bn: int, bk: int, dtype_bytes: float = 2,
               order: str = "out", m: int = 0, n: int = 0) -> float:
    """VMEM working set of one grid step (the kernel-level T constraint).

    ``dtype_bytes`` is the operand width the mapper's R gene selects
    (``precision.bytes_of`` — may be fractional for sub-byte widths).
    Operand and output blocks are double-buffered by the pipeline; the
    accumulator is 4 bytes wide.  The output block is (bm, bn) for 'out',
    the (bm, n) stripe for 'a' and the (m, bn) stripe for 'b'."""
    out_elems = {"out": bm * bn, "a": bm * n, "b": m * bn}[order]
    out_bytes = 2 if dtype_bytes == 2 else 4      # bf16 or the 4-byte acc
    acc_elems = out_elems if (order == "out" or out_bytes != 4) else 0
    return (2 * (bm * bk + bk * bn) * dtype_bytes
            + 2 * out_elems * out_bytes + acc_elems * 4)
