"""Operation and byte counts of the kernels and the step, against hand
counts at small shapes. The counts that depend on the architecture are
its reference module's (``bench/configs/<reference>.py``)."""
import math
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

import bench_tiny  # noqa: E402
from bench.harness import registry  # noqa: E402

S = {"layers": 2, "d_model": 8, "d_ff": 16, "heads": 4, "kv_heads": 2,
     "head_dim": 2, "vocab": 10}
# S with 8 experts, 2 of them per token
S_MOE = dict(S, experts=8, top_k=2)
DECODER = registry.module("configs", "decoder")


def _metric(name):
    return registry.module("metrics", name)


@pytest.fixture
def moe_reference(tmp_path):
    """The tiny expert reference, in a copy of bench/ beside decoder.py."""
    bench_dir, _, _ = bench_tiny.make(tmp_path, bench_tiny.MOE_LIKE,
                                      "tiny-moe")
    for f in bench_tiny.MOE_FILES:
        (bench_dir / "configs" / f).write_text(
            (bench_tiny.FIXTURES / f).read_text())
    return registry.module("configs", "moe_decoder", bench_dir=bench_dir)


def test_matmul_params():
    # per layer: q 8*8 + k 8*4 + v 8*4 + o 8*8 + 3 * 8*16 = 576; head 80
    assert DECODER.matmul_params(S) == 2 * 576 + 80


def test_matmul_params_counts_routed_experts_only(moe_reference):
    # per layer: attention 8*8 + 8*4 + 8*4 + 8*8 = 192; 2 of the 8
    # experts, 3 * 8*16 each = 768; router 8*8 = 64; head 80
    assert moe_reference.matmul_params(S_MOE) == 2 * (192 + 768 + 64) + 80


def test_plane_elements():
    assert DECODER.plane_elements(S, ["wq", "embed"], 0) == 2 * 64 + 80
    assert DECODER.plane_elements(S, [], 0) == 0


def test_plane_elements_leave_out_leaves_under_the_threshold(moe_reference):
    # wq has 64 elements a layer, under 65: listed but not a plane; the
    # embedding's 80 are; so is each expert leaf (8 * 8*16 = 1024 a
    # layer), and the router's 8*8 = 64 is not
    assert DECODER.plane_elements(S, ["wq", "embed"], 65) == 80
    assert DECODER.plane_elements(S, ["wq", "embed"], 81) == 0
    assert moe_reference.plane_elements(
        S_MOE, ["w_gate", "w_up", "w_down", "router"], 65) == 2 * 3 * 1024
    assert moe_reference.plane_elements(S_MOE, ["router"], 64) == 2 * 64


def _program_planes(conf, ref, conv):
    """Elements of the leaves the program stores as byte planes."""
    import jax
    from repro.dist.spec import DIST, SINGLE, build_spec_tree
    from repro.models.init import param_shapes

    shapes, metas = param_shapes(conv.program_config(conf, ref.sizes(conf)))
    specs = jax.tree_util.tree_leaves(build_spec_tree(shapes, metas, SINGLE),
                                      is_leaf=lambda x: hasattr(x, "kind"))
    return sum(math.prod(x.logical) * x.reps for x in specs
               if x.kind == DIST)


@pytest.mark.parametrize("conf", [
    registry.item("configs", "qwen3-1.7b"),
    registry.item("configs", "chatglm3-6b"),
    bench_tiny.QWEN_LIKE, bench_tiny.GLM_LIKE, bench_tiny.MOE_LIKE,
], ids=["qwen3-1.7b", "chatglm3-6b", "tiny-qwen", "tiny-glm",
        "tiny-mixtral"])
def test_plane_elements_are_the_programs_planes(conf, moe_reference):
    configs = pathlib.Path(moe_reference.__file__).parent
    ref = registry.module("configs", conf["reference"]["module"],
                          bench_dir=configs.parent)
    conv = registry.module("configs", conf["program"]["convert"],
                           bench_dir=configs.parent)
    planes = conf["weight_planes"]
    assert ref.plane_elements(ref.sizes(conf), planes["leaves"],
                              planes["min_elements"]) == _program_planes(
                                  conf, ref, conv)


def test_attention_layers(moe_reference):
    assert DECODER.attention_layers(S) == 2
    assert moe_reference.attention_layers(S_MOE) == 2


def test_paged_attend_work():
    # contexts 3 and 5: 8 tokens; ops 4*8*H*hd*L, bytes 2*8*KV*hd*4*L
    ops, nbytes = _metric("paged_attend_roofline").work(S, 2, [3, 5], 4)
    assert ops == 4 * 8 * 4 * 2 * 2
    assert nbytes == 2 * 8 * 2 * 2 * 4 * 2


def test_flash_prefill_work():
    # S=4 causal: 10 (q, k) pairs; ops 4*H*hd*10*L; q,k,v,o once
    ops, nbytes = _metric("flash_prefill_roofline").work(S, 2, 4, 4)
    assert ops == 4 * 4 * 2 * 10 * 2
    assert nbytes == (2 * 4 + 2 * 2) * 4 * 2 * 4 * 2


def test_bitunpack_work():
    # 3 calls, 2-byte planes: read 2 + write 4 bytes per element
    assert _metric("bitunpack_roofline.decode").work(
        DECODER.plane_elements(S, ["wq"], 0), 2, 3) == 3 * 2 * 64 * 6


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
           "sizes": S, "counts": {"attention_layers": 2}, "kv_itemsize": 4}
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
    rec = {"peaks": PEAKS, "devices": 1,
           "counts": {"matmul_params": DECODER.matmul_params(S)},
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


def test_itl_p95_longprompt_reads_what_itl_p95_reads():
    rec = {"itl_s": [0.1 * i for i in range(1, 41)]}
    got = _metric("itl_p95_ms.longprompt").read(rec)
    assert got == _metric("itl_p95_ms").read(rec)
    assert got["unit"] == "ms" and got["gaps"] == 40
    assert _metric("itl_p95_ms.longprompt").read({"itl_s": []}) is None
