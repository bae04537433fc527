"""engine.host_puts_per_token.throughput: the reader of
``engine.host_puts_per_token`` in the closed-loop cell, where it moves
``output_tokens_per_s`` rather than ``tpot_p90_ms``."""

import pathlib

from bench.run import load_metric

read = load_metric(pathlib.Path(__file__).resolve().parents[1],
                   "engine.host_puts_per_token")
