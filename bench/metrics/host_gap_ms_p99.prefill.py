"""host_gap_ms_p99.prefill: 99th percentile of the serving engine's host
gaps in the long-prompt cell (``serve.gap``; see
``host_gap_ms_p50.decode``). A stall between admissions and ticks shows
here, and its ``gap_share.*`` fields say which span covered it."""
from bench.harness.engine_spans import span_ms


def read(rec):
    return span_ms(rec, "serve.gap", 99, gaps=True)
