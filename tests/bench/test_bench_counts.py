"""Operation and byte counts of the kernels and the step, against hand
counts at small shapes."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import counts, registry  # noqa: E402

S = {"layers": 2, "d_model": 8, "d_ff": 16, "heads": 4, "kv_heads": 2,
     "head_dim": 2, "vocab": 10}


def _metric(name):
    return registry.module("metrics", name)


def test_matmul_params():
    # per layer: q 8*8 + k 8*4 + v 8*4 + o 8*8 + 3 * 8*16 = 576; head 80
    assert counts.matmul_params(S) == 2 * 576 + 80


def test_plane_elements():
    assert counts.plane_elements(S, ["wq", "embed"]) == 2 * 64 + 80
    assert counts.plane_elements(S, []) == 0


def test_paged_attend_work():
    # contexts 3 and 5: 8 tokens; ops 4*8*H*hd*L, bytes 2*8*KV*hd*4*L
    ops, nbytes = _metric("paged_attend_roofline").work(S, [3, 5], 4)
    assert ops == 4 * 8 * 4 * 2 * 2
    assert nbytes == 2 * 8 * 2 * 2 * 4 * 2


def test_flash_prefill_work():
    # S=4 causal: 10 (q, k) pairs; ops 4*H*hd*10*L; q,k,v,o once
    ops, nbytes = _metric("flash_prefill_roofline").work(S, 4, 4)
    assert ops == 4 * 4 * 2 * 10 * 2
    assert nbytes == (2 * 4 + 2 * 2) * 4 * 2 * 4 * 2


def test_bitunpack_work():
    # 3 calls, 2-byte planes: read 2 + write 4 bytes per element
    assert _metric("bitunpack_roofline.decode").work(
        S, ["wq"], 2, 3) == 3 * 2 * 64 * 6


def _trace(kernel_s):
    class R:
        window_s, busy_s = 2.0, 1.5

        def kernel_s(self, pattern):
            return kernel_s.get(pattern, 0.0)
    return R()


PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_roofline_share_and_bound():
    rec = {"trace": _trace({"paged_attend": 4.0}), "peaks": PEAKS,
           "traced_calls": {"ticks": [[3, 5]], "admits": []},
           "sizes": S, "kv_itemsize": 4}
    got = _metric("paged_attend_roofline").read(rec)
    # bytes 512 / 10 = 51.2 s least vs 4 s measured: over 100%, which
    # only a count set too high gives; the reader does not clip it
    assert got["value"] == pytest.approx(100 * 51.2 / 4.0)
    assert got["bound"] == "memory"


def test_readers_return_nothing_without_a_trace():
    rec = {"trace": None, "peaks": PEAKS, "traced_calls": None,
           "spans": {}, "sizes": S}
    for name in ("paged_attend_roofline", "flash_prefill_roofline",
                 "bitunpack_roofline.decode", "idle_share.decode",
                 "idle_share.prefill", "mfu.decode", "mfu.prefill",
                 "tick_ms_p50.decode", "tick_ms_p50.prefill",
                 "admit_ms_p50.prefill"):
        assert _metric(name).read(rec) is None


@pytest.mark.parametrize("name", ["idle_share.decode", "idle_share.prefill"])
def test_idle_share(name):
    got = _metric(name).read({"trace": _trace({})})
    assert got == {"value": pytest.approx(25.0), "unit": "%",
                   "window_s": 2.0}


def test_mfu_decode_and_prefill():
    # 2 * (2 * 576 + 80) = 2464 operations a token; 10 tokens in 2 s is
    # 12320 a second, on one chip of peak 100: 12320%
    rec = {"peaks": PEAKS, "sizes": S, "devices": 1,
           "spans": {"decode_tick": [1.5, 0.5], "admit": [2.0]},
           "decode_tokens": 10, "admit_tokens": [10]}
    dec = _metric("mfu.decode").read(rec)
    pre = _metric("mfu.prefill").read(rec)
    assert dec["value"] == pre["value"] == pytest.approx(12320.0)
    assert (dec["ticks"], pre["admits"]) == (2, 1)
    assert _metric("mfu.prefill").read(dict(rec, admit_tokens=[])) is None


@pytest.mark.parametrize("name,span", [("tick_ms_p50.decode", "decode_tick"),
                                       ("tick_ms_p50.prefill", "decode_tick"),
                                       ("admit_ms_p50.prefill", "admit")])
def test_span_medians(name, span):
    got = _metric(name).read({"spans": {span: [0.3, 0.1, 0.2]}})
    assert got == {"value": pytest.approx(200.0), "unit": "ms", "calls": 3}
