"""Tiny stand-ins for the benchmark's cells, for CPU tests: a copy of
``bench/`` with configurations, traffic mixes and limits at sizes a
test run can hold, and the cells' BENCHMARK.json entries."""
from __future__ import annotations

import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

QWEN_LIKE = {
    "name": "tiny-qwen", "source": "test", "model_type": "qwen3",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 2048, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "attention_bias": False, "tie_word_embeddings": False, "dtype": "f32",
    "reduced": [],
    "program": {"arch": "qwen3-1.7b", "fields": {
        "num_layers": "num_hidden_layers", "d_model": "hidden_size",
        "num_heads": "num_attention_heads",
        "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
        "d_ff": "intermediate_size", "vocab_size": "vocab_size",
        "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
        "qkv_bias": "attention_bias",
        "tie_embeddings": "tie_word_embeddings"},
        "convert": "decoder_program"},
    "weight_planes": {"leaves": ["embed", "head", "wq", "wk", "wv", "wo",
                                 "w_gate", "w_up", "w_down"],
                      "min_elements": 65536},
    "reference": {"module": "decoder", "sizes": {
        "layers": "num_hidden_layers", "d_model": "hidden_size",
        "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
        "head_dim": "head_dim", "d_ff": "intermediate_size",
        "vocab": "vocab_size", "norm_eps": "rms_norm_eps",
        "rope_theta": "rope_theta"},
        "rope_pairs": "half", "rotary_dim": 16, "qk_norm": True,
        "qkv_bias": False},
}

GLM_LIKE = {
    "name": "tiny-glm", "source": "test", "model_type": "chatglm",
    "hidden_size": 64, "ffn_hidden_size": 128, "num_layers": 2,
    "num_attention_heads": 8, "multi_query_group_num": 2, "kv_channels": 16,
    "padded_vocab_size": 2048, "layernorm_epsilon": 1e-5, "rope_base": 10000,
    "add_qkv_bias": True, "dtype": "f32", "reduced": [],
    "program": {"arch": "chatglm3-6b", "fields": {
        "num_layers": "num_layers", "d_model": "hidden_size",
        "num_heads": "num_attention_heads",
        "num_kv_heads": "multi_query_group_num", "head_dim": "kv_channels",
        "d_ff": "ffn_hidden_size", "vocab_size": "padded_vocab_size",
        "rope_theta": "rope_base", "norm_eps": "layernorm_epsilon",
        "qkv_bias": "add_qkv_bias"},
        "convert": "decoder_program"},
    "weight_planes": QWEN_LIKE["weight_planes"],
    "reference": {"module": "decoder", "sizes": {
        "layers": "num_layers", "d_model": "hidden_size",
        "heads": "num_attention_heads", "kv_heads": "multi_query_group_num",
        "head_dim": "kv_channels", "d_ff": "ffn_hidden_size",
        "vocab": "padded_vocab_size", "norm_eps": "layernorm_epsilon",
        "rope_theta": "rope_base"},
        "rope_pairs": "interleaved", "rotary_dim": 8, "qk_norm": False,
        "qkv_bias": True},
}

# A sparse-expert decoder on the program's existing expert mixer (the
# registry's mixtral-8x7b, its sliding window switched off so the paged
# engine takes it). Its reference and converter are not in bench/: they
# are the files FIXTURES holds, written into a copy by the test that
# adds this architecture.
MOE_LIKE = {
    "name": "tiny-mixtral", "source": "test", "model_type": "mixtral",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 2048, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
    "num_local_experts": 8, "num_experts_per_tok": 2,
    "sliding_window": None, "tie_word_embeddings": False, "dtype": "f32",
    "reduced": [],
    "program": {"arch": "mixtral-8x7b", "fields": {
        "num_layers": "num_hidden_layers", "d_model": "hidden_size",
        "num_heads": "num_attention_heads",
        "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
        "d_ff": "intermediate_size", "vocab_size": "vocab_size",
        "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
        "num_experts": "num_local_experts",
        "top_k": "num_experts_per_tok",
        "sliding_window": "sliding_window",
        "tie_embeddings": "tie_word_embeddings"},
        "convert": "moe_program"},
    "weight_planes": {"leaves": ["embed", "head", "wq", "wk", "wv", "wo",
                                 "w_gate", "w_up", "w_down", "router"],
                      "min_elements": 65536},
    "reference": {"module": "moe_decoder", "sizes": {
        "layers": "num_hidden_layers", "d_model": "hidden_size",
        "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
        "head_dim": "head_dim", "d_ff": "intermediate_size",
        "vocab": "vocab_size", "norm_eps": "rms_norm_eps",
        "rope_theta": "rope_theta", "experts": "num_local_experts",
        "top_k": "num_experts_per_tok"},
        "rope_pairs": "half", "rotary_dim": 16, "qk_norm": False,
        "qkv_bias": False},
}
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
MOE_FILES = ("moe_decoder.py", "moe_program.py")

MIXES = {
    "tiny-offline": {
        "driver": "serve", "arrivals": {"kind": "offline"}, "block": 16,
        "prompt_len": {"choices": [16, 32], "weights": [0.5, 0.5]},
        "output_len": {"lognormal_median": 8, "sigma": 0.5, "min": 4,
                       "max": 16},
        "plan": {"round_to": 2, "mode": "truncate", "act_round_to": 4},
        "engine": {"max_slots": 4, "page_size": 16, "num_pages": 16},
        "trace": {"start_s": 0.5, "seconds": 0.5},
    },
    # long outputs and more slots: many served tokens to compare, so
    # the control's rare flips show on every seed
    "tiny-decode": {
        "driver": "serve", "arrivals": {"kind": "offline"}, "block": 16,
        "prompt_len": {"choices": [16, 32], "weights": [0.5, 0.5]},
        "output_len": {"lognormal_median": 24, "sigma": 0.5, "min": 8,
                       "max": 64},
        "plan": {"round_to": 2, "mode": "truncate", "act_round_to": 4},
        "engine": {"max_slots": 8, "page_size": 16, "num_pages": 64},
        "trace": {"start_s": 0.5, "seconds": 0.5},
    },
    "tiny-poisson": {
        "driver": "serve", "arrivals": {"kind": "poisson", "rate_per_s": 8},
        "block": 16,
        "prompt_len": {"choices": [16, 48], "weights": [0.6, 0.4]},
        "output_len": {"lognormal_median": 4, "sigma": 0.5, "min": 2,
                       "max": 8},
        "plan": {"round_to": 2, "mode": "truncate", "act_round_to": 4},
        "engine": {"max_slots": 4, "page_size": 16, "num_pages": 16},
        "trace": {"start_s": 0.5, "seconds": 0.5},
    },
    # the program's expert dispatch keeps at most 8 tokens per expert
    # (its capacity floor) at these sizes, and drops the rest; the
    # reference drops none. A token sends top_k entries to top_k
    # distinct experts, so an expert is offered at most one entry per
    # token: 4 slots x top_k 2 = 8 decode entries in all, and prompts
    # of at most 8 tokens (each a page multiple, since the engine
    # prefills expert models at their exact length, which the warm-up's
    # page buckets then cover), drop nothing.
    "tiny-moe": {
        "driver": "serve", "arrivals": {"kind": "offline"}, "block": 16,
        "prompt_len": {"choices": [4, 8], "weights": [0.5, 0.5]},
        "output_len": {"lognormal_median": 16, "sigma": 0.5, "min": 8,
                       "max": 32},
        "plan": {"round_to": 2, "mode": "truncate", "act_round_to": 4},
        "engine": {"max_slots": 4, "page_size": 4, "num_pages": 64},
        "trace": {"start_s": 0.5, "seconds": 0.5},
    },
}

# on the CPU the program computes in full fp32, so a served token's
# logit lies below the reference's best by rounding alone (~1e-6); the
# bf16 control reads ~1e-3 and more at these sizes
LIMIT = 1e-4


def make(tmp_path: pathlib.Path, config: dict, mix_name: str):
    """(bench_dir, bench dict, cell name) of one tiny cell."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    name = f"{config['name']}.{mix_name}"
    (bench_dir / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (bench_dir / "traffic" / f"{mix_name}.json").write_text(
        json.dumps(MIXES[mix_name]))
    (bench_dir / "checks" / f"{name}.json").write_text(json.dumps({
        "numbers": {"gap_per_near_tie": {"tau": 0.02, "min_near_ties": 20,
                                          "limit": LIMIT}},
        "sample": {"max_tokens": 2000, "max_requests": 200},
    }))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = {
        "workloads": [{"name": name, "config": config["name"],
                       "traffic": mix_name, "chips": 1}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [],
    }
    return bench_dir, bench, name
