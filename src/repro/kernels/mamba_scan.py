"""Chunked selective-scan Pallas kernel (Mamba-1 recurrence, TPU target).

    h_t = exp(dt_t ⊗ A) * h_{t-1} + (dt_t * x_t) ⊗ B_t
    y_t = <h_t, C_t> + D * x_t

Grid = (batch, d_inner blocks, seq chunks); the chunk axis is sequential
('arbitrary') and the recurrent state h lives in VMEM scratch, persisting
across chunk steps — the paper's T axis is the (chunk, d_block) tile, the O
axis is the chunk-major traversal that keeps h stationary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref, *,
                 chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a_log = a_ref[...]                        # (dblk, N) — negative values
    d_skip = d_ref[...]                       # (1, dblk)

    def step(t, h):
        xt = x_ref[0, t]                      # (dblk,)
        dtt = dt_ref[0, t]                    # (dblk,)
        bt = b_ref[0, t]                      # (N,)
        ct = c_ref[0, t]                      # (N,)
        decay = jnp.exp(dtt[:, None] * a_log)             # (dblk, N)
        h = decay * h + (dtt * xt)[:, None] * bt[None, :]
        yt = jnp.sum(h * ct[None, :], axis=1) + d_skip[0] * xt
        y_ref[0, t] = yt.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def vmem_bytes(chunk: int, d_block: int, n: int,
               dtype_bytes: float = 4) -> float:
    """VMEM working set of one scan grid step: x/dt/b/c/y chunk blocks and
    the A/D parameter blocks at the operand width, plus the fp32 recurrent
    state scratch (d_block, N)."""
    operands = (3 * chunk * d_block + 2 * chunk * n
                + d_block * n + d_block) * dtype_bytes
    return operands + d_block * n * 4               # h scratch (fp32)


def mamba_scan(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
               c: jnp.ndarray, a_log_neg: jnp.ndarray, d_skip: jnp.ndarray,
               *, chunk: int = 128, d_block: int = 512,
               interpret: bool = False) -> jnp.ndarray:
    """x, dt: (B, L, D); b, c: (B, L, N); a_log_neg: (D, N) (= -exp(A_log));
    d_skip: (D,).  Returns y: (B, L, D)."""
    B, L, D = x.shape
    N = b.shape[-1]
    chunk = min(chunk, L)
    d_block = min(d_block, D)
    assert L % chunk == 0 and D % d_block == 0
    gl, gd = L // chunk, D // d_block

    grid = (B, gd, gl)
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda bb, dd, cc: (bb, cc, dd)),
            pl.BlockSpec((1, chunk, d_block), lambda bb, dd, cc: (bb, cc, dd)),
            pl.BlockSpec((1, chunk, N), lambda bb, dd, cc: (bb, cc, 0)),
            pl.BlockSpec((1, chunk, N), lambda bb, dd, cc: (bb, cc, 0)),
            pl.BlockSpec((d_block, N), lambda bb, dd, cc: (dd, 0)),
            pl.BlockSpec((1, d_block), lambda bb, dd, cc: (0, dd)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block),
                               lambda bb, dd, cc: (bb, cc, dd)),
        out_shape=jax.ShapeDtypeStruct((B, L, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((d_block, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, b, c, a_log_neg, d_skip.reshape(1, -1))
