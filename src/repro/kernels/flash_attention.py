"""Causal flash-attention Pallas kernel (TPU target, GQA-aware wrapper).

Blocking scheme == the `flash_jnp` twin in repro.models.attention:
grid = (batch*kv_head*group, Q blocks, KV blocks), KV innermost; running
(max, sum, acc) live in VMEM scratch across the KV loop (the O axis:
Q-block stationary, online softmax).  Block sizes are the T axis; causal
block-skipping prunes fully-masked KV blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the kernel computes in float32; full-precision MXU passes keep it within
# float32 tolerance of the reference (one bf16 pass would not)
_F32 = jax.lax.Precision.HIGHEST


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, bq: int, bkv: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, d)
        v = v_ref[0].astype(jnp.float32)
        logits = jnp.dot(q, k.T, precision=_F32,
                         preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, bkv), 0)
            kv_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32,
                                                        (bq, bkv), 1)
            logits = jnp.where(q_pos >= kv_pos, logits, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, precision=_F32, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip blocks strictly above the diagonal
        pl.when(ki * bkv <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == n_kv - 1)
    def _store():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = 256, bkv: int = 256,
                    scale: float | None = None,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (H, Sq, d), k/v: (H, Skv, d) — single batch-flattened head axis.
    GQA callers repeat/flatten (batch, kv_head, group) into H."""
    h, sq, d = q.shape
    skv = k.shape[1]
    bq, bkv = min(bq, sq), min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    gq, gkv = sq // bq, skv // bkv
    scale = scale if scale is not None else d ** -0.5

    return pl.pallas_call(
        functools.partial(_flash_kernel, n_kv=gkv, bq=bq, bkv=bkv,
                          causal=causal, scale=scale),
        grid=(h, gq, gkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)


def vmem_bytes(bq: int, bkv: int, d: int, dtype_bytes: float = 2) -> float:
    """VMEM working set of one flash grid step: Q/K/V operand blocks at the
    R-selected width plus the fp32 running-max/sum/accumulator scratch."""
    operands = (bq * d + 2 * bkv * d) * dtype_bytes
    scratch = (2 * bq + bq * d) * 4                 # m, l, acc (fp32)
    return operands + bq * d * 4 + scratch          # + fp32 output block


def flash_attention_bshd(q, k, v, *, causal=True, bq=256, bkv=256,
                         interpret=False):
    """(B, S, H, d) GQA layout convenience wrapper."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1
                    ).reshape(b * hq, skv, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1
                    ).reshape(b * hq, skv, d)
    o = flash_attention(qf, kf, vf, causal=causal, bq=bq, bkv=bkv,
                        interpret=interpret)
    return o.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
