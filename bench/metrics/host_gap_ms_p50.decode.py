"""host_gap_ms_p50.decode: median host gap of the serving engine in the
decode cell: from a step program's ids reaching the host, with a slot
still active, to the dispatch of the next step program (``serve.gap``).
The device has nothing of the engine's queued in it."""
from bench.harness.engine_spans import span_ms


def read(rec):
    return span_ms(rec, "serve.gap", 50, gaps=True)
