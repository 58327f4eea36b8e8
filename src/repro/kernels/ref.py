"""Pure-jnp oracles for every kernel (the ground truth in kernel tests).

Float products run at full float32 precision: an accelerator's default
(one bf16 pass) would make the oracle the least accurate side."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jax.lax.Precision.HIGHEST


def matmul_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Integer operands give the exact int32 product (as the kernel does);
    floating operands accumulate in float32 and return the operand dtype."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        return jnp.dot(x, y, preferred_element_type=jnp.int32)
    return jnp.dot(x.astype(jnp.float32), y.astype(jnp.float32),
                   precision=_F32).astype(x.dtype)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, scale=None) -> jnp.ndarray:
    """q: (H, Sq, d), k/v: (H, Skv, d)."""
    h, sq, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32), precision=_F32)
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :]
        logits = jnp.where(mask[None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32),
                      precision=_F32).astype(q.dtype)


def mamba_scan_ref(x, dt, b, c, a_log_neg, d_skip):
    """Sequential lax.scan oracle of the selective-scan recurrence.
    x, dt: (B, L, D); b, c: (B, L, N); a_log_neg: (D, N); d_skip: (D,)."""
    B, L, D = x.shape
    N = b.shape[-1]

    def step(h, inputs):
        xt, dtt, bt, ct = inputs               # (B,D) (B,D) (B,N) (B,N)
        decay = jnp.exp(dtt[..., None] * a_log_neg[None])     # (B,D,N)
        h = decay * h + (dtt * xt)[..., None] * bt[:, None, :]
        yt = jnp.sum(h * ct[:, None, :], axis=-1) + d_skip[None] * xt
        return h, yt

    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(b, 1, 0).astype(jnp.float32),
          jnp.moveaxis(c, 1, 0).astype(jnp.float32))
    h0 = jnp.zeros((B, D, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)
