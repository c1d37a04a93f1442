"""tick_ms_p50.decode: median of one ``ServeEngine.decode_tick()`` call
on the host clock (staging, the decode step, sampling and the ids back
on the host), in the decode cell."""
from bench.harness.spans import span_ms_p50


def read(rec):
    return span_ms_p50(rec, "decode_tick")
