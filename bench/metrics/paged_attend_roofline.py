"""paged_attend_roofline: the paged decode-attention kernel's least
time over its device time in the traced window.

Per decode tick and layer, each active request of context c reads its
keys and values once, 2 x c x KV x head_dim x itemsize bytes, and takes
4 x c x H x head_dim operations (scores and weighted sum). The least
time is the larger of bytes / HBM bandwidth and operations / bf16 peak;
``bound`` says which."""

KERNEL = "paged_attend"


def work(s: dict, contexts, itemsize: int):
    """(operations, bytes) of one tick over requests of these contexts."""
    c = sum(contexts)
    ops = 4 * c * s["heads"] * s["head_dim"] * s["layers"]
    nbytes = 2 * c * s["kv_heads"] * s["head_dim"] * itemsize * s["layers"]
    return ops, nbytes


def read(rec):
    red, calls, peaks = rec.get("trace"), rec.get("traced_calls"), rec["peaks"]
    if red is None or not calls or not calls["ticks"] or peaks is None:
        return None
    t = red.kernel_s(KERNEL)
    if t <= 0:
        return None
    ops = nbytes = 0
    for ctxs in calls["ticks"]:
        o, b = work(rec["sizes"], ctxs, rec["kv_itemsize"])
        ops, nbytes = ops + o, nbytes + b
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return {"value": 100 * max(t_ops, t_mem) / t, "unit": "%",
            "bound": "memory" if t_mem >= t_ops else "compute",
            "kernel_s": t, "ticks": len(calls["ticks"])}
