"""The check that decides ``correct``: served tokens against the
float32 reference.

Once the window has closed and the program is freed, a sample of the
finished requests, drawn from the seed and always holding the longest,
is run through the config's block (``bench/reference/<block>.py``:
its ``plain_weights`` and ``logits_at``) over its prompt and served
tokens.  The number compared is the widest gap by which a served token's
logit lies below the reference's best logit at that position: greedy
decoding of a correct program picks the reference's first choice up to
rounding, so the gap stays near 0.

``control=True`` reads the same number for the fp8 control: at each
position, the token that the fp8 forward puts first, measured against
the float32 reference.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference.common import served_rows, widest_gap

Served = Dict[int, Tuple[np.ndarray, List[int]]]
SAMPLE_SALT = 0x5EED


def sample(served: Served, n: int, seed: int) -> List[int]:
    """Up to ``n`` uids: the longest request, then others drawn from the
    seed."""
    uids = sorted(served)
    if not uids:
        return []
    length = lambda u: len(served[u][0]) + len(served[u][1])  # noqa: E731
    longest = max(uids, key=lambda u: (length(u), -u))
    rest = [u for u in uids if u != longest]
    rng = np.random.default_rng([seed, SAMPLE_SALT])
    picked = rng.permutation(rest)[:max(n - 1, 0)].tolist()
    return [longest] + sorted(picked)


def widest_gaps(block, spec, seed: int, served: Served,
                uids: Sequence[int], control: bool = False) -> dict:
    """Widest gap over the sampled requests, by ``block``'s reference;
    with ``control`` the same for the fp8 control's own first
    choices."""
    import jax

    w = block.plain_weights(spec, seed)
    worst, worst_ctl, tokens = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for u in uids:
            prompt, gen = served[u]
            seq = list(prompt) + list(gen[:-1])
            rows = served_rows(len(prompt), len(gen))
            ref = block.logits_at(w, spec, seq, rows)
            worst = max(worst, widest_gap(ref, gen))
            tokens += len(gen)
            if control:
                low = block.logits_at(w, spec, seq, rows, precision="fp8")
                worst_ctl = max(worst_ctl, widest_gap(
                    ref, np.argmax(low, axis=-1)))
    del w
    out = {"widest_gap": worst, "requests": len(uids), "tokens": tokens}
    if control:
        out["control_widest_gap"] = worst_ctl
    return out
