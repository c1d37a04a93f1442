"""Attention correctness: tiled vs dense oracle, windows, caches, decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (
    KVCache,
    PagedKVCache,
    attend_decode,
    attend_decode_paged,
    attend_tiled,
    init_cache,
)


def _dense_oracle(q, k, v, causal, window, q_offset=0):
    """Straightforward masked softmax attention (fp32)."""
    B, Sq, Kv, G, hd = q.shape
    Sk = k.shape[1]
    qp = q_offset + np.arange(Sq)
    kp = np.arange(Sk)
    mask = np.ones((Sq, Sk), bool)
    if causal:
        mask &= qp[:, None] >= kp[None, :]
    if window is not None:
        mask &= (qp[:, None] - kp[None, :]) < window
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k) * (hd**-0.5)
    s = jnp.where(jnp.asarray(mask)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bkgqh", p, v)
    return out.transpose(0, 3, 1, 2, 4)


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(0, 1, shape), jnp.float32
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 8, 24])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_tiled_matches_dense(causal, window, chunk):
    B, S, Kv, G, hd = 2, 64, 2, 2, 16
    q = _rand((B, S, Kv, G, hd), 0)
    k = _rand((B, S, Kv, hd), 1)
    v = _rand((B, S, Kv, hd), 2)
    if window is not None and not causal:
        pytest.skip("window only defined for causal here")
    got = attend_tiled(q, k, v, causal=causal, window=window, chunk=chunk)
    want = _dense_oracle(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal_skip", [True, False])
def test_causal_skip_equivalence(causal_skip):
    """The triangular-exact path must equal the masked-rectangle baseline."""
    B, S, Kv, G, hd = 1, 32, 1, 2, 8
    q = _rand((B, S, Kv, G, hd), 3)
    k = _rand((B, S, Kv, hd), 4)
    v = _rand((B, S, Kv, hd), 5)
    got = attend_tiled(
        q, k, v, causal=True, window=None, chunk=8, causal_skip=causal_skip
    )
    want = _dense_oracle(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_decode_matches_prefill_continuation():
    """Decoding token t over a linear cache == full attention at position t."""
    B, S, Kv, G, hd = 2, 17, 2, 2, 8
    k = _rand((B, S, Kv, hd), 6)
    v = _rand((B, S, Kv, hd), 7)
    q_all = _rand((B, S, Kv, G, hd), 8)
    want = _dense_oracle(q_all, k, v, causal=True, window=None)

    cache = init_cache(B, S, Kv, hd, jnp.float32)
    cache = KVCache(k, v, jnp.asarray(S, jnp.int32))
    # check the last position via attend_decode
    got = attend_decode(
        q_all[:, -1:], cache, ring=False, window=None
    )
    np.testing.assert_allclose(
        np.asarray(got[:, 0]), np.asarray(want[:, -1]), rtol=2e-5, atol=2e-5
    )


def test_ring_cache_window_decode():
    """Ring-buffer decode == windowed attention over the full history."""
    B, Kv, G, hd, W = 1, 1, 2, 8, 8
    total = 29
    k_hist = _rand((B, total, Kv, hd), 9)
    v_hist = _rand((B, total, Kv, hd), 10)
    q = _rand((B, 1, Kv, G, hd), 11)

    # build ring cache as decode would have: slot j holds latest pos == j mod W
    pos = total - 1
    kc = jnp.zeros((B, W, Kv, hd), jnp.float32)
    vc = jnp.zeros((B, W, Kv, hd), jnp.float32)
    for t in range(total):
        kc = kc.at[:, t % W].set(k_hist[:, t])
        vc = vc.at[:, t % W].set(v_hist[:, t])
    cache = KVCache(kc, vc, jnp.asarray(total, jnp.int32))
    got = attend_decode(q, cache, ring=True, window=W)

    want = _dense_oracle(
        q, k_hist, v_hist, causal=True, window=W, q_offset=pos
    )
    np.testing.assert_allclose(
        np.asarray(got[:, 0]), np.asarray(want[:, 0]), rtol=2e-5, atol=2e-5
    )


def test_prefill_offset_chunks():
    """q_offset (prefill continuation) produces the same result as slicing
    full attention."""
    B, S, Kv, G, hd = 1, 48, 1, 1, 8
    q = _rand((B, S, Kv, G, hd), 12)
    k = _rand((B, S, Kv, hd), 13)
    v = _rand((B, S, Kv, hd), 14)
    full = attend_tiled(q, k, v, causal=True, window=None, chunk=16)
    tail = attend_tiled(
        q[:, 32:], k, v, causal=True, window=None, chunk=16, q_offset=32
    )
    np.testing.assert_allclose(
        np.asarray(full[:, 32:]), np.asarray(tail), rtol=2e-5, atol=2e-5
    )


def test_int8_prefill_decode_close_to_fp():
    """QuantKVCache prefill+decode ≈ fp cache path (per-slot scales)."""
    from repro.models.attention import (
        QuantKVCache, _quantize_kv, init_cache, mha,
    )
    from repro.configs.registry import get_config, reduced
    from repro.models.env import Env
    from repro.models.init import init_params

    cfg = reduced(get_config("qwen3-1.7b"))
    env = Env(attn_chunk=8)
    env8 = Env(attn_chunk=8, int8_kv=True)
    params, _ = init_params(cfg, jax.random.PRNGKey(0), tp=1)
    w = jax.tree_util.tree_map(lambda a: a[0], params["groups"][0]["p0"])["attn"]
    B, S = 2, 16
    x = _rand((B, S, cfg.d_model), 20) * 0.3

    cache_fp = init_cache(B, S + 2, cfg.num_kv_heads, cfg.head_dim, jnp.float32)
    cache_q = init_cache(B, S + 2, cfg.num_kv_heads, cfg.head_dim, jnp.int8)
    y_fp, cache_fp = mha(x, w, cfg, env, mode="prefill", cache=cache_fp)
    y_q, cache_q = mha(x, w, cfg, env8, mode="prefill", cache=cache_q)
    assert isinstance(cache_q, QuantKVCache)
    np.testing.assert_allclose(np.asarray(y_fp), np.asarray(y_q), rtol=0.05, atol=0.02)

    xt = _rand((B, 1, cfg.d_model), 21) * 0.3
    d_fp, _ = mha(xt, w, cfg, env, mode="decode", cache=cache_fp, pos_offset=S)
    d_q, _ = mha(xt, w, cfg, env8, mode="decode", cache=cache_q, pos_offset=S)
    np.testing.assert_allclose(np.asarray(d_fp), np.asarray(d_q), rtol=0.08, atol=0.02)

    # quantizer itself: roundtrip error bounded by scale/2
    k = _rand((2, 4, 2, 16), 22)
    kq, sc = _quantize_kv(k)
    deq = np.asarray(kq, np.float32) * np.asarray(sc)[..., None]
    assert np.max(np.abs(deq - np.asarray(k))) <= np.max(np.asarray(sc)) * 0.51


def test_paged_decode_kernel_matches_dense_and_skips_retired_rows():
    # two live slots with pages reserved past their tokens, and a
    # retired one: its row all trash (the pool's last row), its pos run
    # far past the table, as the engine's ballast slots leave them
    page, hd = 8, 128
    k_pool = _rand((10, page, 2, hd), 1)
    v_pool = _rand((10, page, 2, hd), 2)
    table = jnp.asarray([[3, 0, 5, 1], [2, 7, 4, 8], [9, 9, 9, 9]], jnp.int32)
    cache = PagedKVCache(k_pool, v_pool, jnp.asarray([11, 25, 1000]))
    q = _rand((3, 1, 2, 2, hd), 3)
    got = np.asarray(attend_decode_paged(q, cache, table, impl="pallas"))
    want = np.asarray(attend_decode_paged(q, cache, table, impl="dense"))
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)
    np.testing.assert_array_equal(got[2], np.zeros_like(got[2]))
