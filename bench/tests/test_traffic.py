"""The three mixes: the same seed gives the same requests, and another
seed sends the same multiset of sizes and gaps in another order."""

import collections

import numpy as np
import pytest

from bench import traffic

SEED = 2**33 + 5


def _gen(name, seed, seconds=30.0):
    mix = traffic.load_mix(name)
    return traffic.generate(mix, seed=seed, seconds=seconds, rate=1.5,
                            vocab=151936, max_len=4096)


def _flat(tr):
    return [(r.rid, r.stream, r.offset, r.max_new, r.follow_up,
             r.prompt.tobytes()) for s in tr.streams for r in s]


@pytest.mark.parametrize("name", ["chat", "docs", "sessions"])
def test_same_seed_same_requests(name):
    a, b = _gen(name, SEED), _gen(name, SEED)
    assert _flat(a) == _flat(b)
    assert [p.tobytes() for p in a.warm_prompts] == \
        [p.tobytes() for p in b.warm_prompts]
    assert _flat(a) != _flat(_gen(name, SEED + 1))


def _shape(tr):
    return [(s[0].offset, tuple((len(r.prompt), r.max_new, r.offset)
                                for r in s)) for s in tr.streams]


@pytest.mark.parametrize("name", ["chat", "docs", "sessions"])
def test_other_seed_same_work_at_the_same_times(name):
    """Another seed changes the token ids, never the sizes or times."""
    a, b = _gen(name, SEED), _gen(name, 12345)
    assert _shape(a) == _shape(b)
    lo, hi = a.window
    if name != "docs":
        n = sum(lo <= s[0].offset < hi for s in a.streams)
        assert n == round(1.5 * 30)


@pytest.mark.parametrize("name", ["chat", "docs", "sessions"])
def test_lengths_within_the_mix(name):
    mix = traffic.load_mix(name)
    tr = _gen(name, SEED)
    for s in tr.streams:
        for r in s:
            assert len(r.prompt) + r.max_new <= 4096
            assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
            assert r.prompt.min() >= 0 and r.prompt.max() < 151936


def test_sessions_share_history():
    tr = _gen("sessions", SEED)
    multi = [s for s in tr.streams if len(s) > 1]
    assert multi
    for s in multi:
        for a, b in zip(s, s[1:]):
            # each turn resends the previous prompt and the recorded reply
            assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
            assert len(b.prompt) > len(a.prompt) + a.max_new
        assert any(np.array_equal(s[0].prompt[:1024], w)
                   for w in tr.warm_prompts)
