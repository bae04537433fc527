"""model.decode_device_ms: device time of the decode step per step, in
ms: the union of device 0's op intervals inside each ``jit_serve_decode``
execution that lies wholly in the traced window, over their number
(``bench/lib/layers.py``).  None where the program names no such step."""

from bench.lib import layers


def read(rec):
    prof = layers.for_run(rec)
    return None if prof is None else layers.decode_device_ms(prof)
