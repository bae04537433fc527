"""One generator for every traffic mix: reads a mix file, yields requests.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

- ``loop``: ``"open"`` (independent users, Poisson arrivals at
  ``rate_rps``) or ``"closed"`` (``clients`` callers, each sends its next
  request when its last one is done);
- ``requests``: the kinds of request, each a prompt length, an output
  length and a weight (how often it is sent), with an optional ``name``;
- ``block``: how many requests make one block (see below);
- ``batch_size``: the engine's batch size (its KV page pool and warm
  shapes); ``drain_s``: how long the run follows requests after the
  window; ``check_requests``: how many finished requests are compared
  with the reference;
- ``source`` and ``why``: where the lengths come from, and what the mix
  stands for.

Every seed sees the same work at the same times.  The requests come in
blocks of ``block``: each block holds every kind of request at its
weight (largest remainders first) and, for an open loop, the same
inter-arrival gaps (the exponential's quantiles at ``(i + 0.5) / block``,
scaled to a mean of exactly ``1 / rate_rps``).  Each block shuffles the
requests and the gaps on its own, in an order fixed by the mix file
alone; the run's seed draws only the token ids, uniform over the
vocabulary.  So two seeds differ in content, never in the sizes or the
arrivals: queueing at 0.8 of the knee depends on the order of arrivals
so much that a per-seed order moved the 90th percentile of time to first
token by a third between seeds, against a few percent between two runs
of one seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from typing import Iterator, List, Optional, Tuple

import numpy as np

LOOPS = ("open", "closed")


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str
    kinds: Tuple[Tuple[int, int], ...]   # (prompt length, output length)
    weights: Tuple[float, ...]
    block: int
    rate_rps: Optional[float] = None
    clients: Optional[int] = None
    batch_size: int = 1
    drain_s: float = 30.0
    check_requests: int = 8     # finished requests compared with the reference

    @property
    def prompt_lengths(self) -> List[int]:
        """Every prompt length the mix sends, each once, in order."""
        return sorted({p for p, _ in self.kinds})

    @property
    def max_len(self) -> int:
        """Longest sequence a request of this mix can reach."""
        return max(p + o for p, o in self.kinds)


def load_mix(path: pathlib.Path) -> Mix:
    raw = json.loads(pathlib.Path(path).read_text())
    loop = raw["loop"]
    if loop not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}, got {loop!r}")
    reqs = raw["requests"]
    kinds = tuple((int(r["prompt"]), int(r["output"])) for r in reqs)
    weights = tuple(float(r["weight"]) for r in reqs)
    if not kinds or min(weights) <= 0:
        raise ValueError(f"{path}: requests need at least one kind, each "
                         "with a weight above 0")
    mix = Mix(
        name=pathlib.Path(path).stem, loop=loop, kinds=kinds,
        weights=weights, block=int(raw["block"]),
        rate_rps=raw.get("rate_rps"), clients=raw.get("clients"),
        batch_size=int(raw.get("batch_size", raw.get("clients") or 1)),
        drain_s=float(raw.get("drain_s", 30.0)),
        check_requests=int(raw.get("check_requests", 8)))
    if loop == "open" and not (mix.rate_rps and mix.rate_rps > 0):
        raise ValueError(f"{path}: an open loop needs rate_rps > 0")
    if loop == "closed" and not (mix.clients and mix.clients > 0):
        raise ValueError(f"{path}: a closed loop needs clients > 0")
    if min(p for p, _ in kinds) < 1:
        raise ValueError(f"{path}: every prompt needs a token")
    if min(o for _, o in kinds) < 2:
        raise ValueError(f"{path}: outputs need at least 2 tokens so that "
                         "time per output token is defined")
    return mix


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the generator plans it."""
    index: int
    prompt: np.ndarray          # (prompt_len,) int32
    max_new_tokens: int
    gap_s: float = 0.0          # open loop: seconds after the previous one


def block_kinds(mix: Mix) -> List[Tuple[int, int]]:
    """The block's requests: each kind ``weight / sum * block`` times,
    rounded by largest remainder so that they sum to ``block``."""
    total = sum(mix.weights)
    exact = [w / total * mix.block for w in mix.weights]
    counts = [int(math.floor(e)) for e in exact]
    order = sorted(range(len(exact)), key=lambda i: counts[i] - exact[i])
    for i in order[:mix.block - sum(counts)]:
        counts[i] += 1
    return [k for k, c in zip(mix.kinds, counts) for _ in range(c)]


def block_gaps(mix: Mix) -> List[float]:
    """Exponential quantiles at ``(i + 0.5) / block``, mean ``1/rate``."""
    raw = [-math.log(1.0 - (i + 0.5) / mix.block) for i in range(mix.block)]
    scale = 1.0 / (mix.rate_rps * (sum(raw) / len(raw)))
    return [g * scale for g in raw]


# The order of sizes and arrivals; the same for every run of every mix.
SCHEDULE_SEED = 0


def generate(mix: Mix, seed: int, vocab_size: int) -> Iterator[Planned]:
    """The mix's requests in order, block after block, without end."""
    order = np.random.default_rng(SCHEDULE_SEED)
    tokens = np.random.default_rng(seed)
    kinds = block_kinds(mix)
    gaps = block_gaps(mix) if mix.loop == "open" else [0.0] * mix.block
    index = 0
    while True:
        k = [kinds[i] for i in order.permutation(mix.block)]
        g = order.permutation(gaps)
        for (prompt_len, out_len), gap in zip(k, g):
            toks = tokens.integers(0, vocab_size, prompt_len, dtype=np.int32)
            yield Planned(index, toks, out_len, float(gap))
            index += 1
