"""engine.decode_idle_ms: device 0's idle time per decode step, in ms:
the gaps of the traced window in which no op ran and whose midpoint lies
inside a ``serve.decode`` span of the serving thread, over the
``jit_serve_decode`` executions inside the same spans
(``bench/lib/layers.py``).  None where the program names no such step."""

from bench.lib import layers


def read(rec):
    prof = layers.for_run(rec)
    return None if prof is None else layers.decode_idle_ms(prof)
