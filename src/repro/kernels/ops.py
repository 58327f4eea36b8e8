"""jit'd public entry points for the Pallas kernels.

On a TPU the `pl.pallas_call`s lower to Mosaic.  On the CPU backend (the
test suite, `JAX_PLATFORMS=cpu`) they run in Pallas's TPU interpret mode,
which emulates the TPU pipeline's block semantics; any other backend is an
error rather than a silent interpreter.  `use_pallas=False` runs the XLA
reference path — that is what the multi-pod dry-run lowers, so compile
artifacts never depend on interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from . import dtype_for_bits, ref
from .flash_attention import flash_attention as _flash
from .flash_attention import flash_attention_bshd as _flash_bshd
from .mamba_scan import mamba_scan as _mamba
from .tiled_matmul import tiled_matmul as _matmul


def _interpret():
    """Compiled on TPU, interpreted on CPU, refused anywhere else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return pltpu.InterpretParams()
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU "
        f"backend; the default backend is {backend!r}")


def _cast(arrays, bits, kind):
    """R-axis width threading: ``bits`` (a mapper ``Mapping.repr_bits``)
    selects the executed kernel dtype; ``None`` keeps the caller's dtypes.
    Static under jit, so each width compiles its own program."""
    if bits is None:
        return arrays
    dt = dtype_for_bits(bits, kind)
    return tuple(a.astype(dt) for a in arrays)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "order", "bits",
                                    "use_pallas"))
def matmul(x, y, *, bm=128, bn=128, bk=128, order="out", bits=None,
           use_pallas=True):
    x, y = _cast((x, y), bits, "matmul")
    if not use_pallas:
        return ref.matmul_ref(x, y)
    return _matmul(x, y, bm=bm, bn=bn, bk=bk, order=order,
                   interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bkv", "bits",
                                    "use_pallas"))
def attention(q, k, v, *, causal=True, bq=256, bkv=256, bits=None,
              use_pallas=True):
    q, k, v = _cast((q, k, v), bits, "attention")
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal)
    return _flash(q, k, v, causal=causal, bq=bq, bkv=bkv,
                  interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bkv", "use_pallas"))
def attention_bshd(q, k, v, *, causal=True, bq=256, bkv=256,
                   use_pallas=True):
    if not use_pallas:
        h = q.shape[2] // k.shape[2]
        kk = jnp.repeat(k, h, axis=2).transpose(0, 2, 1, 3)
        vv = jnp.repeat(v, h, axis=2).transpose(0, 2, 1, 3)
        qq = q.transpose(0, 2, 1, 3)
        b, hh, sq, d = qq.shape
        o = ref.attention_ref(qq.reshape(b * hh, sq, d),
                              kk.reshape(b * hh, -1, d),
                              vv.reshape(b * hh, -1, d), causal=causal)
        return o.reshape(b, hh, sq, d).transpose(0, 2, 1, 3)
    return _flash_bshd(q, k, v, causal=causal, bq=bq, bkv=bkv,
                       interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("chunk", "d_block", "bits", "use_pallas"))
def mamba_scan(x, dt, b, c, a_log_neg, d_skip, *, chunk=128, d_block=512,
               bits=None, use_pallas=True):
    x, dt, b, c = _cast((x, dt, b, c), bits, "mamba")
    if not use_pallas:
        return ref.mamba_scan_ref(x, dt, b, c, a_log_neg, d_skip)
    return _mamba(x, dt, b, c, a_log_neg, d_skip, chunk=chunk,
                  d_block=d_block, interpret=_interpret())
