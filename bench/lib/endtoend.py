"""The end-to-end metrics, from the harness's own host-clock readings.

Each function takes the run's :class:`~bench.lib.harness.RunRecord`.
Percentiles interpolate linearly between order statistics (numpy's
default).
"""

from __future__ import annotations

import numpy as np


def tpot_ms(rec) -> list:
    """(completion - first token) / (output tokens - 1), per completed
    request that fell due in the window."""
    return [1e3 * (r.token_times[-1] - r.token_times[0])
            / (len(r.token_times) - 1)
            for r in rec.requests
            if r.completed and r.due < rec.seconds
            and len(r.token_times) >= 2]


def tpot_p90_ms(rec) -> float:
    return float(np.percentile(tpot_ms(rec), 90))


def output_tokens_per_s(rec) -> float:
    """Every output token produced in the window, over its seconds."""
    return sum(r.tokens_at_close for r in rec.requests) / rec.seconds


METRICS = {
    "tpot_p90_ms": tpot_p90_ms,
    "output_tokens_per_s": output_tokens_per_s,
}
