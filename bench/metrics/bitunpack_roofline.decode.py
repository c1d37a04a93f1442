"""bitunpack_roofline.decode: the weight-plane unpack kernel's least
time over its device time in the traced window of the decode cell.

Every program call (decode tick or admission) unpacks each weight the
format keeps as planes once: it reads ``round_to`` plane bytes and
writes 4 fp32 bytes per element. The kernel does no arithmetic to
speak of, so the least time is bytes / HBM bandwidth. The elements
kept as planes are the reference's count (``record["counts"]``)."""

KERNEL = "bitunpack"


def work(elements: int, round_to: int, calls: int) -> int:
    """Bytes the kernel must move over ``calls`` program calls that each
    unpack ``elements`` plane-stored elements."""
    return calls * elements * (round_to + 4)


def read(rec):
    red, calls, peaks = rec.get("trace"), rec.get("traced_calls"), rec["peaks"]
    if red is None or not calls or peaks is None:
        return None
    n = len(calls["ticks"]) + len(calls["admits"])
    t = red.kernel_s(KERNEL)
    if n == 0 or t <= 0:
        return None
    nbytes = work(rec["counts"]["plane_elements"],
                  rec["mix"]["plan"]["round_to"], n)
    return {"value": 100 * nbytes / peaks["hbm_bytes_per_s"] / t,
            "unit": "%", "bound": "memory", "kernel_s": t, "calls": n}
