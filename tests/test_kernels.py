"""Per-kernel allclose tests: Pallas (interpret) vs pure-jnp oracle.

Sweeps shapes / round_to / value distributions, plus hypothesis property
tests on the pack/unpack invariants.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.bitpack import bitpack_2d
from repro.kernels.bitunpack import bitunpack_2d
from repro.kernels.l2norm import l2norm_sq_2d

SHAPES_2D = [(256, 128), (512, 128), (1024, 128)]
ROUND_TOS = [1, 2, 3, 4]


def _rand(shape, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32))


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("round_to", ROUND_TOS)
def test_bitpack_kernel_matches_ref(shape, round_to):
    w = _rand(shape, seed=round_to)
    got = bitpack_2d(w, round_to, interpret=True)
    want = ref.bitpack_ref(w, round_to)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("round_to", ROUND_TOS)
def test_bitunpack_kernel_matches_ref(shape, round_to):
    w = _rand(shape, seed=17 + round_to, scale=3.0)
    planes = ref.bitpack_ref(w, round_to)
    got = bitunpack_2d(planes, interpret=True)
    want = ref.bitunpack_ref(planes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(512, 128), (2048, 128)])
def test_l2norm_kernel_matches_ref(shape):
    w = _rand(shape, seed=3, scale=0.1)
    got = l2norm_sq_2d(w, interpret=True)
    want = ref.l2norm_sq_ref(w)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@pytest.mark.parametrize(
    "shape", [(7,), (130,), (64, 33), (3, 5, 7), (1,), (40000,)]
)
@pytest.mark.parametrize("round_to", ROUND_TOS)
def test_ops_quantize_arbitrary_shapes(shape, round_to):
    w = _rand(shape, seed=round_to * 11, scale=2.0)
    got = ops.quantize(w, round_to)
    want = ref.quantize_ref(w, round_to)
    assert got.shape == w.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_round_to_4_is_identity():
    w = _rand((1000,), seed=5)
    np.testing.assert_array_equal(np.asarray(ops.quantize(w, 4)), np.asarray(w))


def test_round_to_2_is_bfloat16_truncation():
    """Paper's 16-bit format (1s+8e+7m) is exactly bf16 round-toward-zero."""
    w = _rand((4096,), seed=9, scale=10.0)
    q = np.asarray(ops.quantize(w, 2))
    # truncation: uint32 view with low 16 bits cleared
    u = np.asarray(w).view(np.uint32) & np.uint32(0xFFFF0000)
    np.testing.assert_array_equal(q.view(np.uint32), u)


@given(
    st.lists(
        st.floats(
            allow_nan=False, allow_infinity=False, width=32
        ),
        min_size=1,
        max_size=64,
    ),
    st.sampled_from(ROUND_TOS),
)
@settings(max_examples=60, deadline=None)
def test_property_truncation_invariants(vals, round_to):
    """Truncation: |q| <= |w|, sign preserved, idempotent, error < 2^(drop) ulp."""
    w = jnp.asarray(np.asarray(vals, np.float32))
    q = np.asarray(ref.quantize_ref(w, round_to))
    wn = np.asarray(w)
    # sign preserved (zero maps to +/-0)
    assert np.all((q >= 0) == (wn >= 0) | (q == 0))
    # magnitude never increases under truncation toward zero
    assert np.all(np.abs(q) <= np.abs(wn))
    # idempotent
    q2 = np.asarray(ref.quantize_ref(jnp.asarray(q), round_to))
    np.testing.assert_array_equal(q, q2)
    # relative error bound: dropping d mantissa bits -> rel err < 2^-(kept mantissa)
    kept_mantissa = max(0, 8 * round_to - 9)
    finite = np.abs(wn) > 1e-30
    if kept_mantissa > 0 and finite.any():
        rel = np.abs(q[finite] - wn[finite]) / np.abs(wn[finite])
        assert np.all(rel <= 2.0 ** (-kept_mantissa) + 1e-12)


@given(st.integers(1, 4), st.integers(1, 5000))
@settings(max_examples=30, deadline=None)
def test_property_pack_unpack_roundtrip_on_packed_values(round_to, n):
    """Values already representable in round_to bytes survive exactly."""
    rng = np.random.default_rng(n)
    w = rng.normal(0, 1, (n,)).astype(np.float32)
    w = np.asarray(ref.quantize_ref(jnp.asarray(w), round_to))
    q = np.asarray(ref.quantize_ref(jnp.asarray(w), round_to))
    np.testing.assert_array_equal(w, q)


def test_nearest_mode_reduces_bias():
    # truncation is round-toward-zero: |q| <= |w| always, so the magnitude
    # error is systematically negative; round-to-nearest should center it.
    w = _rand((20000,), seed=21, scale=1.0)
    mag_trunc = np.mean(
        np.abs(np.asarray(ref.quantize_ref(w, 2))) - np.abs(np.asarray(w))
    )
    mag_near = np.mean(
        np.abs(np.asarray(ref.quantize_ref(w, 2, mode="nearest")))
        - np.abs(np.asarray(w))
    )
    assert mag_trunc < 0
    assert abs(mag_near) < abs(mag_trunc)


def test_stochastic_mode_unbiased():
    key = jax.random.PRNGKey(0)
    w = jnp.full((50000,), 1.0 + 1e-4, jnp.float32)
    q = ref.quantize_ref(w, 2, mode="stochastic", key=key)
    # expectation of stochastic rounding equals the input
    assert abs(float(jnp.mean(q)) - float(jnp.mean(w))) < 1e-5


def test_special_values_survive():
    w = jnp.asarray([0.0, -0.0, 1.0, -1.0, np.inf, -np.inf], jnp.float32)
    for rt in ROUND_TOS:
        q = np.asarray(ref.quantize_ref(w, rt))
        if rt > 1:
            # 16+ bits keep sign + full exponent + some mantissa:
            # zeros, +/-1 and infinities survive exactly.
            np.testing.assert_array_equal(q[:4], np.asarray(w)[:4])
            assert np.isinf(q[4]) and q[4] > 0
            assert np.isinf(q[5]) and q[5] < 0
        else:
            # 8-bit (sign + 7 exponent bits) loses the exponent LSB: it can
            # represent zero exactly but not 1.0 or inf — as in the paper,
            # 8-bit is only useful very early in training.
            np.testing.assert_array_equal(q[:2], np.asarray(w)[:2])


# ---------------------------------------------------------------------------
# attention kernels: flash prefill + paged decode (interpret-mode parity)
# ---------------------------------------------------------------------------

from repro.kernels.flash_prefill import flash_prefill, flash_prefill_ref
from repro.kernels.paged_attention import paged_attend, paged_attend_ref


def _qkv(B, H, Kv, Sq, Sk, hd, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (B, H, Sq, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, Kv, Sk, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, Kv, Sk, hd)).astype(np.float32))
    return q, k, v


def _dense_softmax_attn(q, k, v, causal, q_offset):
    # plain softmax reference (not the kernel's schedule): allclose only
    B, H, Sq, hd = q.shape
    Kv, Sk = k.shape[1], k.shape[2]
    g = H // Kv
    kh = jnp.repeat(k, g, axis=1)
    vh = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kh) * hd ** -0.5
    if causal:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        mask = qpos >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vh)


@pytest.mark.parametrize("shape", [(1, 2, 1, 256, 256, 128),
                                   (2, 4, 2, 128, 384, 128)])
def test_flash_prefill_kernel_matches_ref_bitwise(shape):
    B, H, Kv, Sq, Sk, hd = shape
    off = Sk - Sq  # chunked prefill: q tile ends the kv sequence
    q, k, v = _qkv(B, H, Kv, Sq, Sk, hd, seed=5)
    got = flash_prefill(q, k, v, causal=True, q_offset=off, interpret=True)
    want = flash_prefill_ref(q, k, v, causal=True, q_offset=off)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(_dense_softmax_attn(q, k, v, True, off)),
        atol=2e-5,
    )


def test_flash_prefill_q_offset_parity():
    # chunked prefill: the q tile sits at the END of the kv sequence
    q, k, v = _qkv(1, 2, 2, 128, 256, 128, seed=9)
    got = flash_prefill(q, k, v, causal=True, q_offset=128, interpret=True)
    want = flash_prefill_ref(q, k, v, causal=True, q_offset=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _paged_setup(B, Kv, G, page, n_pages, num_phys, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(
        rng.normal(0, 1, (B, Kv, G, 128)).astype(np.float32)
    )
    pool_shape = (num_phys, page, Kv, 128)
    k_pool = jnp.asarray(rng.normal(0, 1, pool_shape).astype(np.float32))
    v_pool = jnp.asarray(rng.normal(0, 1, pool_shape).astype(np.float32))
    # distinct physical pages per (slot, logical) entry
    perm = rng.permutation(num_phys)[: B * n_pages]
    table = jnp.asarray(perm.reshape(B, n_pages).astype(np.int32))
    lengths = jnp.asarray(
        rng.integers(1, page * n_pages + 1, (B,)).astype(np.int32)
    )
    return q, k_pool, v_pool, table, lengths


def test_paged_attend_kernel_matches_ref_bitwise():
    q, kp, vp, table, lengths = _paged_setup(3, 2, 2, 8, 4, 16, seed=11)
    got = paged_attend(q, kp, vp, table, lengths, interpret=True)
    want = paged_attend_ref(q, kp, vp, table, lengths)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_attend_matches_dense_softmax():
    q, kp, vp, table, lengths = _paged_setup(2, 2, 4, 8, 4, 12, seed=13)
    out = np.asarray(paged_attend(q, kp, vp, table, lengths, interpret=True))
    B, Kv, G, hd = q.shape
    page, n_pages = kp.shape[1], table.shape[1]
    for b in range(B):
        L = int(lengths[b])
        k = np.asarray(kp)[np.asarray(table)[b]].reshape(-1, Kv, hd)[:L]
        v = np.asarray(vp)[np.asarray(table)[b]].reshape(-1, Kv, hd)[:L]
        s = np.einsum("kgh,pkh->kgp", np.asarray(q)[b], k) * hd ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("kgp,pkh->kgh", p, v)
        np.testing.assert_allclose(out[b], want, atol=2e-5)


def test_paged_attend_page_table_permutation_invariance():
    # scatter the same logical pages to different physical rows: the
    # output must be BITWISE identical — attention walks the table, so
    # physical placement can never leak into the math
    q, kp, vp, table, lengths = _paged_setup(2, 2, 2, 8, 3, 12, seed=17)
    base = np.asarray(paged_attend(q, kp, vp, table, lengths, interpret=True))
    rng = np.random.default_rng(23)
    perm = rng.permutation(kp.shape[0])
    inv = np.argsort(perm)
    kp2 = jnp.asarray(np.asarray(kp)[perm])
    vp2 = jnp.asarray(np.asarray(vp)[perm])
    table2 = jnp.asarray(inv[np.asarray(table)].astype(np.int32))
    moved = np.asarray(
        paged_attend(q, kp2, vp2, table2, lengths, interpret=True)
    )
    np.testing.assert_array_equal(base, moved)


def _reserved_tail_setup(fill, seed=29):
    # slot rows own distinct real pages past their live prefix (reserved
    # at admission, never written), filled with ``fill``; the same rows
    # again with every tail entry on one trash page (the pool's last row)
    B, Kv, G, page, n_pages = 3, 2, 2, 8, 5
    q, kp, vp, table, _ = _paged_setup(B, Kv, G, page, n_pages, 16, seed=seed)
    lengths = np.array([1, 2 * page + 3, 3 * page], np.int32)
    table = np.asarray(table)
    live = -(-lengths // page)
    tail = np.arange(n_pages)[None, :] >= live[:, None]
    # row 16, in no slot's table, is the trash page
    kp, vp = (np.concatenate([x, np.zeros_like(x[:1])]) for x in (kp, vp))
    kp[table[tail]] = fill
    vp[table[tail]] = fill
    trash_table = np.where(tail, kp.shape[0] - 1, table).astype(np.int32)
    return (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
            jnp.asarray(trash_table), jnp.asarray(lengths))


@pytest.mark.parametrize("fill", [1e6, np.nan])
def test_paged_attend_reserved_tail_bitwise(fill):
    # the walk stops at each row's live prefix: pages past it are never
    # computed on, so what they hold (a NaN would poison any product
    # with a zero weight) cannot reach the output
    q, kp, vp, table, trash_table, lengths = _reserved_tail_setup(fill)
    got = np.asarray(paged_attend(q, kp, vp, table, lengths, interpret=True))
    want = np.asarray(paged_attend_ref(q, kp, vp, table, lengths))
    np.testing.assert_array_equal(got, want)
    assert np.all(np.isfinite(got))
    on_trash = paged_attend(q, kp, vp, trash_table, lengths, interpret=True)
    np.testing.assert_array_equal(got, np.asarray(on_trash))


def test_paged_attend_zero_length_row_is_zero():
    q, kp, vp, table, _, lengths = _reserved_tail_setup(1e6)
    lengths = lengths.at[1].set(0)
    for out in (paged_attend(q, kp, vp, table, lengths, interpret=True),
                paged_attend_ref(q, kp, vp, table, lengths)):
        out = np.asarray(out)
        np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))
        assert np.all(out[[0, 2]] != 0)
