"""tick_ms_p50.prefill: median of one ``ServeEngine.decode_tick()`` call
on the host clock in the long-prompt cell. Every request due waits for
the tick in progress, and for a free slot, which the ticks turn over, so
the tick moves time to first token there."""
from bench.harness.spans import span_ms_p50


def read(rec):
    return span_ms_p50(rec, "decode_tick")
