"""mfu.prefill: admission's share of the chip's peak: 2 x matmul
parameters per prompt token (embedding excluded, head included; the
attention's own operations left out) x prompt tokens admitted, over the
summed ``admit`` time (``counts.step_mfu``)."""
from bench.harness.counts import step_mfu


def read(rec):
    admits = rec["spans"].get("admit") or []
    toks = rec.get("admit_tokens") or []
    if len(toks) != len(admits):
        return None
    return step_mfu(rec, sum(toks), sum(admits),
                    prompt_tokens=sum(toks), admits=len(admits))
