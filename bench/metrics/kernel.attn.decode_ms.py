"""kernel.attn.decode_ms: device time per decode step, in ms, of the ops
whose ``tf_op`` name path starts with ``jit(serve_decode)`` and holds the
``attn`` scope (the attention core of ``models/attention.py`` and
``kvcache/paged.py``; ``bench/lib/layers.py``).  None where the program
names no such step."""

from bench.lib import layers


def read(rec):
    prof = layers.for_run(rec)
    return None if prof is None else layers.scope_ms(prof, "attn")
