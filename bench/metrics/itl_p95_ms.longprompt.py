"""itl_p95_ms.longprompt: ``itl_p95_ms`` in the long-prompt cell, an
end-to-end metric of its own so that it has a bound of its own. There
admissions queue between decode ticks and a gap is one tick plus the
prefills run before it, so its 95th percentile moves between those
counts from run to run, far more than the decode cell's does."""
from bench.harness import registry

read = registry.beside(__file__, "itl_p95_ms").read
