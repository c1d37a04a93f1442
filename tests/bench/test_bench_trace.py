"""The trace reduction, on small traces recorded on a TPU v5e chip
(``fixtures/<cell>.xplane.pb.gz``, 3 s of the decode cell and 6 s of
the long-prompt cell) and on hand-made events."""
import gzip
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import trace  # noqa: E402

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
SPANS = {"admit", "decode_tick", "gen_wait", trace.WINDOW}
# what each recorded window holds: (window s, busy s, host spans,
# {kernel: device s}, the kernel that took most time)
RECORDED = {
    # qwen3-1.7b decode, 12 slots, pages of 64: 18 decode ticks
    "decode": (2.888888585, 2.813110544, {"decode_tick": 18},
               {"paged_attend": 0.940459341, "bitunpack": 0.33950728,
                "bitpack": 0.404542518}, "paged_attend"),
    # chatglm3-6b long prompts, 32 slots, pages of 128: 28 ticks, 9 prefills
    "longprompt": (5.774083378, 5.583271634, {"decode_tick": 28, "admit": 9},
                   {"paged_attend": 1.926813855,
                    "flash_prefill": 0.297353666}, "paged_attend"),
}


@pytest.fixture(scope="module", params=sorted(RECORDED))
def chip(request, tmp_path_factory):
    gz = FIXTURES / f"{request.param}.xplane.pb.gz"
    path = tmp_path_factory.mktemp("trace") / f"{request.param}.xplane.pb"
    path.write_bytes(gzip.decompress(gz.read_bytes()))
    t = trace.load(str(path), SPANS)
    return RECORDED[request.param], t, trace.reduce(t)


def test_chip_trace_window_and_busy_union(chip):
    (window, busy, spans, _, _), t, r = chip
    assert r.devices == 1
    assert r.window_s == pytest.approx(window)
    assert r.busy_s == pytest.approx(busy)
    assert 0 < r.busy_s <= r.window_s
    for name, n in spans.items():
        assert sum(1 for s, _, _ in t.spans if s == name) == n


def test_chip_trace_kernel_time_by_name(chip):
    (_, _, _, kernels, first), _, r = chip
    for name, seconds in kernels.items():
        assert r.kernel_s(name) == pytest.approx(seconds)
    assert "while" not in r.op_s  # loops span their body's ops
    top = r.breakdown()["device_ops"]
    assert top[0][0] == first and len(top) == 10


def test_chip_trace_one_chip_has_no_exposed_collective(chip):
    _, _, r = chip
    assert r.exposed_collective_s == 0.0


def test_chip_trace_idle_gaps_sum_to_idle_time(chip):
    _, _, r = chip
    idle = sum(g for _, g in r.idle_gaps)
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert r.idle_gaps[0][0] == "decode_tick"


@pytest.mark.parametrize("name,kind", [
    ("%paged_attend.24 = f32[12,8,2,128] custom-call(...)", "paged_attend"),
    ("fusion.12", "fusion"),
    ("%all-gather-start.3 = (u8[4,8]) all-gather-start(...)",
     "all-gather-start"),
    ("%broadcast.137.clone = f32[] broadcast()", "broadcast.137.clone"),
])
def test_op_kind(name, kind):
    assert trace.op_kind(name) == kind


def test_hand_made_events():
    # device: compute 0-10, collective 8-14 (4 exposed), idle 14-20,
    # compute 20-25; host span "decode_tick" covers 12-22
    ms = 1_000_000
    t = trace.Trace(
        ops={"/device:TPU:0": [
            ("%fusion.1 = f32[] fusion()", 0, 10 * ms),
            ("%all-reduce.2 = f32[] all-reduce()", 8 * ms, 14 * ms),
            ("%while.3 = () while()", 0, 25 * ms),
            ("%fusion.4 = f32[] fusion()", 20 * ms, 25 * ms),
        ]},
        spans=[(trace.WINDOW, 0, 30 * ms), ("decode_tick", 12 * ms, 22 * ms),
               ("admit", 26 * ms, 30 * ms)],
    )
    r = trace.reduce(t)
    assert r.window_s == pytest.approx(0.030)
    assert r.busy_s == pytest.approx(0.025)  # the loop spans 0-25
    assert r.exposed_collective_s == pytest.approx(0.004)
    assert r.op_s == pytest.approx({"fusion": 0.015, "all-reduce": 0.006})
    assert r.idle_gaps == [("admit", pytest.approx(0.005))]


def test_union_and_subtract():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace._subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
