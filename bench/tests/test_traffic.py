"""The generator: every seed offers the same length mix in another order,
with exactly the stated deterministic share in each stratum."""

from __future__ import annotations

import itertools

from bench import spec, traffic


def _first(mix, seed, n):
    return list(itertools.islice(iter(traffic.make(mix, seed, 32064)), n))


def test_strata_hold_the_same_lengths_for_every_seed():
    mix = spec.load_json(spec.BENCH / "traffic" / "chat-det50.json")
    k = mix["stratum"]
    a, b = _first(mix, 1, 2 * k), _first(mix, 2 ** 31 + 7, 2 * k)
    for lo in (0, k):
        sa, sb = a[lo:lo + k], b[lo:lo + k]
        assert sorted(len(r.prompt) for r in sa) == sorted(len(r.prompt) for r in sb)
        assert sorted(r.max_new_tokens for r in sa) == sorted(r.max_new_tokens for r in sb)
        assert sum(r.deterministic for r in sa) == k // 2
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(16 <= len(r.prompt) <= 1024 and 16 <= r.max_new_tokens <= 512
               for r in a)


def test_same_seed_same_requests():
    mix = spec.load_json(spec.BENCH / "traffic" / "chat-det0.json")
    a, b = _first(mix, 99, 20), _first(mix, 99, 20)
    assert a == b and not any(r.deterministic for r in a)


def test_quantiles_follow_the_fit():
    fit = {"mean": 304, "median": 136, "min": 1, "max": 10 ** 9}
    lens = traffic.quantile_lengths(fit, 2001)
    assert abs(int(lens[1000]) - 136) <= 1  # the median
