"""The step's share of the peak, which every ``mfu.*`` reader gives. The
counts it rests on are the architecture's: the driver records them once
a run, from the cell's reference module (``bench/configs/<reference>.py``),
as ``record["counts"]``."""


def step_mfu(rec: dict, n_tokens: int, seconds: float, **base):
    """The metric every ``mfu.*`` reader gives: 2 x matmul parameters x
    ``n_tokens`` over ``seconds`` of the program's calls, as a share of
    the chips' bf16 peak (the TPU runs fp32 matmuls at default
    precision as bf16 passes). None where nothing was counted."""
    if rec["peaks"] is None or not n_tokens or seconds <= 0:
        return None
    flops = 2 * rec["counts"]["matmul_params"] * n_tokens
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["devices"]
    return dict({"value": 100 * flops / seconds / peak, "unit": "%"}, **base)
