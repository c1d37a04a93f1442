"""itl_p95_ms: 95th percentile of every gap between consecutive output
tokens of a request, both tokens on the host inside the window."""
from bench.harness.spans import percentile


def read(rec):
    gaps = rec.get("itl_s") or []
    if not gaps:
        return None
    return {"value": percentile(gaps, 95) * 1e3, "unit": "ms",
            "gaps": len(gaps)}
