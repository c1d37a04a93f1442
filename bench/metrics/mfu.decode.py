"""mfu.decode: the decode step's share of the chip's peak: 2 x matmul
parameters per token (the reference's ``matmul_params``: embedding
excluded, head included, only the experts a token is routed to) x decoded
tokens, over the summed ``decode_tick`` time (``counts.step_mfu``)."""
from bench.harness.counts import step_mfu


def read(rec):
    ticks = rec["spans"].get("decode_tick") or []
    return step_mfu(rec, rec.get("decode_tokens") or 0, sum(ticks),
                    tokens=rec.get("decode_tokens"), ticks=len(ticks))
