"""flash_prefill_roofline: the flash prefill kernel's least time over
its device time in the traced window.

Per admitted prompt of S tokens and per layer, causal attention takes
4 x H x head_dim x S(S+1)/2 operations (scores and weighted sum over
the lower triangle) and moves q, k, v and the output once:
(2 H + 2 KV) x S x head_dim x itemsize bytes. The least time is the
larger of operations / bf16 peak and bytes / HBM bandwidth."""

KERNEL = "flash_prefill"


def work(s: dict, S: int, itemsize: int):
    ops = 4 * s["heads"] * s["head_dim"] * S * (S + 1) // 2 * s["layers"]
    nbytes = ((2 * s["heads"] + 2 * s["kv_heads"]) * S * s["head_dim"]
              * itemsize * s["layers"])
    return ops, nbytes


def read(rec):
    red, calls, peaks = rec.get("trace"), rec.get("traced_calls"), rec["peaks"]
    if red is None or not calls or not calls["admits"] or peaks is None:
        return None
    t = red.kernel_s(KERNEL)
    if t <= 0:
        return None
    ops = nbytes = 0
    for S in calls["admits"]:
        o, b = work(rec["sizes"], S, rec["kv_itemsize"])
        ops, nbytes = ops + o, nbytes + b
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return {"value": 100 * max(t_ops, t_mem) / t, "unit": "%",
            "bound": "compute" if t_ops >= t_mem else "memory",
            "kernel_s": t, "prefills": len(calls["admits"])}
