"""The traffic generator is seed-deterministic, follows its mix, and
gives every seed the same schedule of sizes and arrivals."""
import collections
import itertools
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import traffic  # noqa: E402

MIX = {
    "arrivals": {"kind": "poisson", "rate_per_s": 4.0},
    "block": 20,
    "prompt_len": {"choices": [128, 256, 512], "weights": [0.5, 0.3, 0.2]},
    "output_len": {"lognormal_median": 512, "sigma": 0.75, "min": 64,
                   "max": 2048},
}


def _take(seed, n, mix=MIX):
    return list(itertools.islice(traffic.Generator(mix, seed, 1000), n))


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**40 + 3])
def test_same_seed_same_requests(seed):
    assert _take(seed, 50) == _take(seed, 50)


def test_other_seed_other_ids_same_schedule():
    a, b = _take(1, 40), _take(2, 40)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [r.due for r in a] == [r.due for r in b]


def test_each_block_holds_the_same_sizes():
    reqs = _take(1, 40)
    first, second = reqs[:20], reqs[20:]
    assert (sorted(len(r.prompt) for r in first)
            == sorted(len(r.prompt) for r in second))
    assert sorted(r.max_new for r in first) == sorted(r.max_new for r in second)
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert np.allclose(sorted(gaps[:20]), sorted(gaps[20:]))
    assert [len(r.prompt) for r in first] != [len(r.prompt) for r in second]


def test_lengths_follow_the_mix():
    counts = collections.Counter(len(r.prompt) for r in _take(3, 20))
    assert counts == {128: 10, 256: 6, 512: 4}
    outs = [r.max_new for r in _take(3, 200)]
    assert min(outs) >= 64 and max(outs) <= 2048
    assert 450 <= np.median(outs) <= 580


def test_poisson_gaps_have_the_rate():
    reqs = _take(4, 400)
    assert reqs[-1].due / len(reqs) == pytest.approx(0.25, rel=0.05)
    assert all(b.due >= a.due for a, b in zip(reqs, reqs[1:]))


def test_offline_requests_are_all_due_at_once():
    mix = dict(MIX, arrivals={"kind": "offline"})
    assert {r.due for r in _take(5, 30, mix)} == {0.0}


def test_prompt_ids_stay_in_vocab():
    ids = [t for r in _take(6, 30) for t in r.prompt]
    assert min(ids) >= 0 and max(ids) < 1000
