"""The traffic generator: same work for every seed, in another order."""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from bench import harness, loadgen

TRAFFIC = {"prompt_lens": {"min": 16, "max": 128, "count": 8, "spacing": "log"},
           "output_lens": {"min": 256, "max": 2048, "count": 8,
                           "spacing": "log_quantiles"}}


def _take(seed, n=64, vocab=1000):
    return list(itertools.islice(loadgen.request_stream(TRAFFIC, seed, vocab), n))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_every_block_holds_every_length_once(seed):
    reqs = _take(harness.derive_seed(seed, "traffic"))
    for b in range(0, len(reqs), 8):
        block = reqs[b:b + 8]
        assert sorted(r.prompt_len for r in block) == \
            loadgen.length_set(TRAFFIC["prompt_lens"])
        assert sorted(r.out_len for r in block) == \
            loadgen.length_set(TRAFFIC["output_lens"])
        assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000 for r in block)


def test_seeds_change_order_and_tokens_not_the_work():
    a, b = _take(1), _take(2)
    assert [r.out_len for r in a] != [r.out_len for r in b]
    assert sorted(r.out_len for r in a) == sorted(r.out_len for r in b)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_same_seed_same_requests():
    a, b = _take(5), _take(5)
    assert all(x.out_len == y.out_len and np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))


def test_derive_seed_uses_every_bit():
    s = 2**31 + 11
    assert harness.derive_seed(s, "x") != harness.derive_seed(s + 2**33, "x")
    assert harness.derive_seed(s, "x") != harness.derive_seed(s, "y")
    assert 0 <= harness.derive_seed(2**62, "weights") < 2**31


@pytest.mark.parametrize("spacing,want", [("log", [16, 32, 64, 128]),
                                          ("log_quantiles", [23, 45, 91, 181])])
def test_length_set_spacings(spacing, want):
    top = 128 if spacing == "log" else 256
    assert loadgen.length_set({"min": 16, "max": top, "count": 4,
                               "spacing": spacing}) == want
