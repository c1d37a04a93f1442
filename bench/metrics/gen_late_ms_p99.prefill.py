"""gen_late_ms_p99.prefill: 99th percentile of how late the open-loop
generator handed a request to the engine's queue after it was due. The
loop is one host thread, so a request due during an admission or a
decode tick waits for it; a starved generator shows here and not as a
fast server."""
from bench.harness.spans import percentile


def read(rec):
    late = rec.get("gen_late_s") or []
    if not late:
        return None
    return {"value": percentile(late, 99) * 1e3, "unit": "ms",
            "requests": len(late)}
