"""kernel.attn.decode_ms.throughput: the reader of
``kernel.attn.decode_ms`` in the closed-loop cell, where it moves
``output_tokens_per_s`` rather than ``tpot_p90_ms``."""

import pathlib

from bench.run import load_metric

read = load_metric(pathlib.Path(__file__).resolve().parents[1],
                   "kernel.attn.decode_ms")
