"""Operation and byte counts of a decoder-only model, from its sizes
(the reference's names, ``bench/configs/<reference>.py:sizes``)."""


def layer_matrices(s: dict) -> dict:
    """Elements of each per-layer weight matrix."""
    d, ff = s["d_model"], s["d_ff"]
    H, K, hd = s["heads"], s["kv_heads"], s["head_dim"]
    return {"wq": d * H * hd, "wk": d * K * hd, "wv": d * K * hd,
            "wo": H * hd * d, "w_gate": d * ff, "w_up": d * ff,
            "w_down": ff * d}


def matmul_params(s: dict) -> int:
    """Parameters a token multiplies through: every layer's matrices
    and the output head; the embedding is a lookup and does not count."""
    return s["layers"] * sum(layer_matrices(s).values()) + s["d_model"] * s["vocab"]


def plane_elements(s: dict, leaves) -> int:
    """Elements of the leaves the serving format keeps as byte planes
    (each is unpacked once per program call)."""
    per = layer_matrices(s)
    n = sum(s["layers"] * per[k] for k in leaves if k in per)
    n += sum(s["d_model"] * s["vocab"] for k in ("embed", "head") if k in leaves)
    return n


def step_mfu(rec: dict, n_tokens: int, seconds: float, **base):
    """The metric every ``mfu.*`` reader gives: 2 x matmul parameters x
    ``n_tokens`` over ``seconds`` of the program's calls, as a share of
    the chips' bf16 peak (the TPU runs fp32 matmuls at default
    precision as bf16 passes). None where nothing was counted."""
    if rec["peaks"] is None or not n_tokens or seconds <= 0:
        return None
    flops = 2 * matmul_params(rec["sizes"]) * n_tokens
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["devices"]
    return dict({"value": 100 * flops / seconds / peak, "unit": "%"}, **base)
