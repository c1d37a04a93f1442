"""ttft_p95_ms: 95th percentile, over every request due in the window,
of the time from when it was due (open loop) to its first token on the
host. A request that never got one counts as infinitely late."""
from bench.harness.spans import percentile


def read(rec):
    ttft = rec.get("ttft_s") or []
    if not ttft:
        return None
    return {"value": percentile(ttft, 95) * 1e3, "unit": "ms",
            "requests": len(ttft)}
