"""admit_wait_ms_p50.prefill: median time an admission blocks for its
first id (``serve.wait`` under ``serve.admit``): the queued prefill,
page insert and sampler, and the copy of the id back to the host."""
from bench.harness.engine_spans import span_ms


def read(rec):
    return span_ms(rec, "serve.admit/serve.wait", 50)
