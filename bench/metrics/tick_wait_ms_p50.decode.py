"""tick_wait_ms_p50.decode: median time a decode tick blocks for its
sampled ids (``serve.wait`` under ``serve.tick``): the queued decode
step and sampler, and the copy of the ids back to the host."""
from bench.harness.engine_spans import span_ms


def read(rec):
    return span_ms(rec, "serve.tick/serve.wait", 50)
