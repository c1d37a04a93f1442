"""admit_ms_p50.prefill: median of one ``ServeEngine.admit()`` call on
the host clock, from staging the prompt to the first id back on the
host (prefill, page insert and sampling)."""
from bench.harness.spans import span_ms_p50


def read(rec):
    return span_ms_p50(rec, "admit")
