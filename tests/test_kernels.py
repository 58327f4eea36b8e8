"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.tiled_matmul import vmem_bytes

RNG = np.random.default_rng(0)


def rand(shape, dtype):
    if dtype == jnp.int8:
        # integer-valued in {-1, 0, 1}: int8 products/sums stay exact, so
        # the quantized R-axis path is checked bit-for-bit vs the oracle
        return jnp.asarray(RNG.integers(-1, 2, shape), jnp.int8)
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("order", ["out", "a", "b"])
@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (128, 128, 128, 64, 64, 64),
    (256, 192, 64, 64, 64, 32),
    (64, 64, 256, 32, 32, 128),
    (128, 256, 128, 128, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_tiled_matmul_sweep(order, m, n, k, bm, bn, bk, dtype):
    x, y = rand((m, k), dtype), rand((k, n), dtype)
    got = ops.matmul(x, y, bm=bm, bn=bn, bk=bk, order=order)
    gold = ref.matmul_ref(x, y)
    tol = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2,
           jnp.int8: 0.0}[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(gold, np.float32),
                               rtol=tol, atol=tol * 8)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,sq,skv,d,bq,bkv", [
    (2, 128, 128, 64, 64, 64),
    (4, 64, 256, 32, 32, 64),
    (1, 256, 256, 128, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(causal, h, sq, skv, d, bq, bkv, dtype):
    if causal and sq != skv:
        pytest.skip("causal requires square for this sweep")
    q, k, v = (rand((h, sq, d), dtype), rand((h, skv, d), dtype),
               rand((h, skv, d), dtype))
    got = ops.attention(q, k, v, causal=causal, bq=bq, bkv=bkv)
    gold = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(gold, np.float32),
                               rtol=tol, atol=tol * 8)


def test_flash_attention_gqa_bshd():
    q = rand((2, 128, 8, 32), jnp.float32)
    k = rand((2, 128, 2, 32), jnp.float32)
    v = rand((2, 128, 2, 32), jnp.float32)
    got = ops.attention_bshd(q, k, v, causal=True, bq=64, bkv=64)
    gold = ops.attention_bshd(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gold),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("B,L,D,N,chunk,dblk", [
    (1, 32, 16, 8, 8, 8),
    (2, 64, 32, 16, 16, 16),
    (2, 128, 64, 8, 32, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_mamba_scan_sweep(B, L, D, N, chunk, dblk, dtype):
    x = rand((B, L, D), dtype) * 0.5
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, L, D)), dtype)
    b = rand((B, L, N), dtype)
    c = rand((B, L, N), dtype)
    a_log = -jnp.asarray(RNG.uniform(0.5, 2.0, (D, N)), jnp.float32)
    d_skip = jnp.ones((D,), jnp.float32)
    got = ops.mamba_scan(x, dt, b, c, a_log, d_skip, chunk=chunk,
                         d_block=dblk)
    gold = ref.mamba_scan_ref(x, dt, b, c, a_log, d_skip)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gold),
                               rtol=2e-4, atol=2e-4)


def test_vmem_budget_helper():
    # the T-axis legality check: a 128^3 bf16 block set fits 16MB VMEM
    assert vmem_bytes(128, 128, 128, 2) < 16 * 2 ** 20
    assert vmem_bytes(2048, 2048, 2048, 2) > 16 * 2 ** 20


def test_vmem_budget_tracks_r_axis_width():
    """The R gene's width reaches the VMEM working set: operand bytes scale
    with bytes_of(bits) (sub-byte widths pack fractionally), fp32
    accumulator cost is width-independent."""
    from repro.core.precision import bytes_of
    from repro.kernels.flash_attention import vmem_bytes as att_vmem
    from repro.kernels.mamba_scan import vmem_bytes as scan_vmem

    ws = [vmem_bytes(128, 128, 128, bytes_of(b)) for b in (4, 8, 16, 32)]
    assert ws == sorted(ws) and ws[0] < ws[1] and ws[2] < ws[3]
    # operand term halves from int8 -> int4; the int32 output blocks and
    # the 4-byte accumulator do not
    acc = 2 * 128 * 128 * 4 + 128 * 128 * 4
    assert (vmem_bytes(128, 128, 128, 1) - acc) == \
        2 * (vmem_bytes(128, 128, 128, 0.5) - acc)
    # the A/B-stationary orders keep a whole output stripe resident
    assert vmem_bytes(128, 128, 128, 4, "a", n=4096) > \
        vmem_bytes(128, 128, 128, 4)
    assert vmem_bytes(128, 128, 128, 4, "b", m=4096) == \
        vmem_bytes(128, 128, 128, 4, "a", n=4096)
    assert att_vmem(128, 128, 64, 2) < 16 * 2 ** 20
    assert scan_vmem(128, 512, 16, 4) < 16 * 2 ** 20
    assert att_vmem(64, 64, 32, 4) > att_vmem(64, 64, 32, 2)
    assert scan_vmem(64, 64, 16, 4) > scan_vmem(64, 64, 16, 2)


def test_ops_bits_threading():
    """ops entry points execute at the R-selected width: bits chooses the
    kernel dtype (and floors at each kernel's narrowest supported width)."""
    x, y = rand((64, 64), jnp.float32), rand((64, 64), jnp.float32)
    assert ops.matmul(x, y, bm=32, bn=32, bk=32, bits=8).dtype == jnp.int32
    assert ops.matmul(x, y, bm=32, bn=32, bk=32,
                      bits=16).dtype == jnp.bfloat16
    assert ops.matmul(x, y, bm=32, bn=32, bk=32, bits=None).dtype == \
        jnp.float32
    q, k, v = (rand((2, 64, 32), jnp.float32) for _ in range(3))
    assert ops.attention(q, k, v, bq=32, bkv=32,
                         bits=8).dtype == jnp.bfloat16   # floor: bf16
    xm = rand((1, 32, 16), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (1, 32, 16)), jnp.float32)
    bm_ = rand((1, 32, 8), jnp.float32)
    cm = rand((1, 32, 8), jnp.float32)
    a_log = -jnp.asarray(RNG.uniform(0.5, 2.0, (16, 8)), jnp.float32)
    d_skip = jnp.ones((16,), jnp.float32)
    out = ops.mamba_scan(xm, dt, bm_, cm, a_log, d_skip, chunk=8,
                         d_block=8, bits=8)              # floor: f32
    assert out.dtype == jnp.float32


def test_kernel_matches_model_flash_path():
    """The Pallas flash kernel and the model's flash_jnp twin agree."""
    from repro.models.attention import _flash_attention_jnp
    q = rand((1, 128, 4, 32), jnp.float32)
    k = rand((1, 128, 2, 32), jnp.float32)
    v = rand((1, 128, 2, 32), jnp.float32)
    jnp_out = _flash_attention_jnp(q, k, v, True, jnp.arange(128),
                                   block_kv=64)
    pallas_out = ops.attention_bshd(q, k, v, causal=True, bq=64, bkv=64)
    np.testing.assert_allclose(np.asarray(jnp_out), np.asarray(pallas_out),
                               rtol=2e-5, atol=2e-4)
