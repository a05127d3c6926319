"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gating import moe_gating
from repro.kernels.ssd_scan import ssd_state_scan

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Sk,H,K,hd,causal", [
    (2, 128, 128, 4, 2, 64, True),
    (1, 256, 256, 8, 8, 128, True),
    (2, 128, 256, 4, 1, 32, True),       # decode-style suffix queries
    (1, 128, 128, 2, 2, 80, False),      # non-128-aligned head dim
    (1, 64, 64, 6, 3, 16, True),
])
def test_flash_attention_shapes(B, Sq, Sk, H, K, hd, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, K, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("Sq,Sk", [
    (1, 1), (200, 200), (300, 300),      # prompt lengths off the 128 tile
    (72, 200),                           # padded suffix queries
])
def test_flash_attention_any_length(Sq, Sk):
    """Default 128 tiles: the wrapper pads both sequences and masks."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, Sq, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, Sk, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, Sk, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, interpret=True)
    want = ref.attention_ref(q, k, v)
    assert out.shape == want.shape
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_attention_grad_matches_reference():
    """jax.grad through the kernel (custom_vjp) equals the reference's."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, 200, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 200, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 200, 2, 32), jnp.float32)
    w = jax.random.normal(ks[3], (2, 200, 4, 32), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(ops.attention(q, k, v) * w)

    grads = {}
    for impl in ("interpret", "ref"):
        with ops.use_impl(impl):
            grads[impl] = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    (l_k, g_k), (l_r, g_r) = grads["interpret"], grads["ref"]
    np.testing.assert_allclose(l_k, l_r, rtol=1e-5)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_chunked_attention_matches_ref():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 512, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 512, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 512, 2, 32), jnp.float32)
    out = ref.attention_chunked(q, k, v, causal=True, chunk_q=128)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

def _decode_inputs(B, Smax, H, K, hd, L):
    """A query and K/V caches of one layer (``L`` None) or stacked over
    ``L`` layers."""
    ks = jax.random.split(KEY, 3)
    cache = ((B,) if L is None else (L, B)) + (Smax, K, hd)
    return (jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32),
            jax.random.normal(ks[1], cache, jnp.float32),
            jax.random.normal(ks[2], cache, jnp.float32))


def _decode_ids(cases, n):
    """One layer's cases keep their ids (the first ``n`` numbers); stacked
    ones show every number and the layer read."""
    return ["-".join(map(str, c[:n])) if c[-2] is None
            else "-".join(map(str, c[:-2])) + f"-L{c[-2]}-layer{c[-1]}"
            for c in cases]


_DECODE_CASES = [
    (2, 512, 8, 2, 64, 300, 128, None, 0),
    (1, 1024, 4, 4, 128, 1024, 256, None, 0),
    (2, 256, 4, 1, 32, 7, 64, None, 0),           # nearly-empty cache
    (3, 384, 6, 2, 48, 200, 128, None, 0),
    # a stacked (L, B, Smax, K, hd) cache read at its first or last layer
    (2, 512, 8, 2, 64, 300, 128, 3, 0),
    (2, 256, 4, 1, 32, 7, 64, 3, 2),
    (3, 384, 6, 2, 48, 200, 128, 2, 1),
    (2, 512, 16, 8, 128, 511, 128, 4, 3),
]


@pytest.mark.parametrize("B,Smax,H,K,hd,length,bk,L,layer", _DECODE_CASES,
                         ids=_decode_ids(_DECODE_CASES, 7))
def test_flash_decode(B, Smax, H, K, hd, length, bk, L, layer):
    q, ck, cv = _decode_inputs(B, Smax, H, K, hd, L)
    out = flash_decode(q, ck, cv, jnp.asarray(length), layer, block_k=bk,
                       interpret=True)
    if L is not None:
        ck, cv = ck[layer], cv[layer]
    want = ref.decode_attention_ref(q, ck, cv, jnp.asarray(length))
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


_RAGGED_CASES = [
    (512, 128, 2, 64, None, 0),
    (300, 128, 2, 64, None, 0),
    # stacked caches: first and last layer, Smax not whole blocks
    (300, 128, 2, 64, 4, 0),
    (300, 128, 8, 128, 4, 3),
    (300, 128, 1, 32, 2, 1),
    (520, 256, 2, 48, 3, 2),
]


@pytest.mark.parametrize("Smax,bk,K,hd,L,layer", _RAGGED_CASES,
                         ids=_decode_ids(_RAGGED_CASES, 2))
def test_flash_decode_ragged_lengths(Smax, bk, K, hd, L, layer):
    """Per-row (B,) lengths, as a continuous batch decodes; a cache that is
    not a whole number of blocks is masked past its end."""
    B, H = 4, 8
    q, ck, cv = _decode_inputs(B, Smax, H, K, hd, L)
    lengths = jnp.asarray([1, 77, Smax, 129], jnp.int32)
    out = flash_decode(q, ck, cv, lengths, layer, block_k=bk, interpret=True)
    if L is not None:
        ck, cv = ck[layer], cv[layer]
    want = ref.decode_attention_ref(q, ck, cv, lengths)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_decode_ref_per_slot_lengths():
    ks = jax.random.split(KEY, 3)
    B, Smax, H, K, hd = 3, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (B, Smax, K, hd), jnp.float32)
    cv = jax.random.normal(ks[2], (B, Smax, K, hd), jnp.float32)
    lengths = jnp.asarray([4, 100, 128])
    out = ref.decode_attention_ref(q, ck, cv, lengths)
    for b in range(B):
        one = ref.decode_attention_ref(q[b:b + 1], ck[b:b + 1], cv[b:b + 1],
                                       jnp.asarray(int(lengths[b])))
        np.testing.assert_allclose(out[b:b + 1], one, atol=1e-6)


# ---------------------------------------------------------------------------
# ssd state scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,C,H,P,N", [
    (2, 8, 4, 16, 16), (1, 16, 2, 32, 64), (3, 4, 1, 8, 8),
])
def test_ssd_state_scan(B, C, H, P, N):
    ks = jax.random.split(KEY, 2)
    xs = jax.random.normal(ks[0], (B, C, H, P, N), jnp.float32)
    a = jax.random.uniform(ks[1], (B, C, H), minval=0.3, maxval=0.99)
    prefix, fin = ssd_state_scan(xs, a, interpret=True)
    pref2, fin2 = ref.ssd_state_scan_ref(xs, a)
    np.testing.assert_allclose(prefix, pref2, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(fin, fin2, atol=1e-5, rtol=1e-5)


def test_ssd_state_scan_init_state():
    ks = jax.random.split(KEY, 3)
    xs = jax.random.normal(ks[0], (1, 4, 2, 8, 8), jnp.float32)
    a = jax.random.uniform(ks[1], (1, 4, 2), minval=0.5, maxval=0.9)
    s0 = jax.random.normal(ks[2], (1, 2, 8, 8), jnp.float32)
    prefix, fin = ssd_state_scan(xs, a, s0, interpret=True)
    pref2, fin2 = ref.ssd_state_scan_ref(xs, a, s0)
    np.testing.assert_allclose(prefix, pref2, atol=1e-5)
    np.testing.assert_allclose(fin, fin2, atol=1e-5)


# ---------------------------------------------------------------------------
# moe gating
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,E,k,bt", [
    (512, 128, 8, 128), (256, 8, 2, 64), (1024, 64, 4, 256), (64, 16, 1, 64),
])
def test_moe_gating(T, E, k, bt):
    logits = jax.random.normal(KEY, (T, E), jnp.float32)
    w, ids = moe_gating(logits, k, block_t=bt, interpret=True)
    w2, ids2 = ref.moe_gating_ref(logits, k)
    assert bool(jnp.all(ids == ids2))
    np.testing.assert_allclose(w, w2, atol=1e-6)


def test_moe_gating_ragged_token_count():
    """A token count off the block is padded and the padding dropped."""
    logits = jax.random.normal(KEY, (300, 64), jnp.float32)
    w, ids = moe_gating(logits, 4, block_t=128, interpret=True)
    w2, ids2 = ref.moe_gating_ref(logits, 4)
    assert w.shape == (300, 4) and bool(jnp.all(ids == ids2))
    np.testing.assert_allclose(w, w2, atol=1e-6)


def test_kernel_impl_follows_platform():
    assert ops.get_impl() == ("pallas" if jax.default_backend() == "tpu"
                              else "ref")
    with ops.use_impl("interpret"):
        assert ops.get_impl() == "interpret"
    assert ops.get_impl() != "interpret"


def test_moe_gating_property():
    pytest.importorskip("hypothesis", reason="property sweep needs hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 4), st.integers(2, 6), st.integers(1, 3))
    def check(bt_pow, e_pow, k):
        T, E = 2 ** (bt_pow + 4), 2 ** e_pow
        k = min(k, E)
        logits = jax.random.normal(jax.random.PRNGKey(T + E + k), (T, E))
        w, ids = moe_gating(logits, k, block_t=T, interpret=True)
        # weights positive, sum to 1, ids unique per row
        assert bool(jnp.all(w > 0))
        np.testing.assert_allclose(jnp.sum(w, -1), jnp.ones(T), atol=1e-5)
        for row in np.asarray(ids):
            assert len(set(row.tolist())) == k

    check()
