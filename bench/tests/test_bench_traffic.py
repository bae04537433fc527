"""The traffic generator: deterministic from the seed, and faithful to
the parameters of each mix file."""

import itertools
import json
import math
import pathlib
import statistics

import numpy as np
import pytest

from bench.lib import traffic

MIXES = sorted((pathlib.Path(__file__).resolve().parents[1] / "traffic")
               .glob("*.json"))


def _take(mix, seed, n, vocab=1000):
    return list(itertools.islice(traffic.generate(mix, seed, vocab), n))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_schedule(path):
    mix = traffic.load_mix(path)
    n = 3 * mix.block
    a, b = _take(mix, 2**40 + 3, n), _take(mix, 2**40 + 3, n)
    c = _take(mix, 2**40 + 4, n)
    key = lambda reqs: [(r.prompt.tolist(), r.max_new_tokens, r.gap_s)  # noqa: E731
                        for r in reqs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # Another seed changes the token ids, never the sizes or arrivals.
    sizes = lambda reqs: [(len(r.prompt), r.max_new_tokens, r.gap_s)  # noqa: E731
                          for r in reqs]
    assert sizes(a) == sizes(c)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_prompt_lengths_come_from_the_buckets(path):
    """Every request is one of the file's kinds, and each block holds
    every kind at its weight, to the nearest request."""
    mix = traffic.load_mix(path)
    raw = json.loads(path.read_text())["requests"]
    kinds = {(r["prompt"], r["output"]) for r in raw}
    reqs = _take(mix, 7, 5 * mix.block, vocab=333)
    assert {(len(r.prompt), r.max_new_tokens) for r in reqs} <= kinds
    assert all(r.prompt.min() >= 0 and r.prompt.max() < 333 for r in reqs)
    total = sum(r["weight"] for r in raw)
    share = {k: sum(r["weight"] for r in raw
                    if (r["prompt"], r["output"]) == k) / total for k in kinds}
    for b in range(5):
        block = reqs[b * mix.block:(b + 1) * mix.block]
        for k, w in share.items():
            count = sum(1 for q in block
                        if (len(q.prompt), q.max_new_tokens) == k)
            assert abs(count - w * mix.block) <= 1
    assert mix.max_len == max(p + o for p, o in kinds)
    assert mix.prompt_lengths == sorted({p for p, _ in kinds})


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_output_lengths_match_the_file(path):
    """The mean output length over whole blocks is the file's, weighted
    by how often each kind is sent, to the rounding of one block."""
    mix = traffic.load_mix(path)
    raw = json.loads(path.read_text())["requests"]
    outs = [r.max_new_tokens for r in _take(mix, 11, 10 * mix.block)]
    want = sum(r["output"] * r["weight"] for r in raw) / \
        sum(r["weight"] for r in raw)
    longest = max(r["output"] for r in raw)
    assert abs(statistics.mean(outs) - want) <= longest / mix.block
    assert min(outs) >= 2


def test_open_loop_rate_matches_the_file():
    paths = [p for p in MIXES if traffic.load_mix(p).loop == "open"]
    assert paths
    for path in paths:
        mix = traffic.load_mix(path)
        n = 20 * mix.block
        gaps = [r.gap_s for r in _take(mix, 3, n)]
        rate = n / sum(gaps)
        # Poisson arrivals: the count in a span has sd sqrt(n); the
        # quantile blocks hold the mean exactly, so 3 sd is generous.
        assert abs(rate - mix.rate_rps) <= 3 * mix.rate_rps / math.sqrt(n)
        # Inter-arrival times are exponential: coefficient of variation 1.
        cv = np.std(gaps) / np.mean(gaps)
        assert 0.8 < cv < 1.2


def test_mix_files_are_refused_when_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    kind = {"prompt": 8, "output": 4, "weight": 1}
    bad.write_text(json.dumps({"loop": "open", "requests": [kind],
                               "block": 4}))
    with pytest.raises(ValueError, match="rate_rps"):
        traffic.load_mix(bad)
    bad.write_text(json.dumps({"loop": "closed", "clients": 2, "block": 4,
                               "requests": [dict(kind, output=1)]}))
    with pytest.raises(ValueError, match="at least 2 tokens"):
        traffic.load_mix(bad)
