"""Property tests (hypothesis) for the SSM substrate invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.ssm import causal_conv1d, chunked_linear_scan


def direct_scan(a, b, h0):
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h
    h_last, hs = jax.lax.scan(step, h0, (jnp.moveaxis(a, 1, 0),
                                         jnp.moveaxis(b, 1, 0)))
    return jnp.moveaxis(hs, 0, 1), h_last


@given(st.integers(1, 33), st.integers(1, 17), st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_chunked_scan_equals_direct_for_any_chunk(L, chunk, seed):
    rng = np.random.default_rng(seed)
    B, D = 2, 3
    a = jnp.asarray(rng.uniform(0.2, 0.99, (B, L, D)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, L, D)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
    got, got_last = chunked_linear_scan(a, b, h0, chunk)
    want, want_last = direct_scan(a, b, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(want_last),
                               rtol=2e-5, atol=2e-5)


@given(st.integers(2, 40), st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_segmented_scan_equals_full_scan(L, seed):
    """Scanning [0:n) then [n:L) with the carried state == one scan —
    the invariant that makes prefill+decode exact for SSM archs."""
    rng = np.random.default_rng(seed)
    n = max(1, L // 2)
    B, D = 1, 4
    a = jnp.asarray(rng.uniform(0.2, 0.99, (B, L, D)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, L, D)), jnp.float32)
    h0 = jnp.zeros((B, D), jnp.float32)
    full, full_last = chunked_linear_scan(a, b, h0, chunk=8)
    h1_all, h1 = chunked_linear_scan(a[:, :n], b[:, :n], h0, chunk=8)
    h2_all, h2 = chunked_linear_scan(a[:, n:], b[:, n:], h1, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1_all, h2_all],
                                                          axis=1)),
                               np.asarray(full), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(full_last),
                               rtol=2e-5, atol=2e-5)


@given(st.integers(1, 24), st.integers(1, 4), st.integers(0, 50))
@settings(max_examples=20, deadline=None)
def test_causal_conv_matches_lax_conv(L, K, seed):
    rng = np.random.default_rng(seed)
    B, D = 2, 3
    x = jnp.asarray(rng.normal(size=(B, L, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, D)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
    y, _ = causal_conv1d(x, w, bias)
    # oracle: depthwise causal conv via lax.conv_general_dilated
    lhs = jnp.moveaxis(x, 2, 1)                       # (B, D, L)
    rhs = jnp.moveaxis(w, 0, 1)[:, None, :]           # (D, 1, K)
    out = jax.lax.conv_general_dilated(
        lhs, rhs, window_strides=(1,), padding=[(K - 1, 0)],
        feature_group_count=D)
    want = jnp.moveaxis(out, 1, 2) + bias
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_conv_streaming_equals_batch():
    """Feeding the conv one token at a time with carried state == batch."""
    rng = np.random.default_rng(0)
    B, L, D, K = 1, 10, 4, 4
    x = jnp.asarray(rng.normal(size=(B, L, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, D)), jnp.float32)
    bias = jnp.zeros((D,), jnp.float32)
    full, _ = causal_conv1d(x, w, bias)
    prev = jnp.zeros((B, K - 1, D), jnp.float32)
    outs = []
    for t in range(L):
        y, prev = causal_conv1d(x[:, t:t + 1], w, bias, prev)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full), rtol=2e-5, atol=2e-5)
