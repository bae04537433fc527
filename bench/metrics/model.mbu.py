"""model.mbu: HBM bytes that serving needs for the model steps whose
tokens arrived in the traced window, over the window's seconds times
the chip's peak HBM bandwidth, in percent.  The bytes (weights once per
step, K/V at the actual context, no activations) are counted by the
config's block (``bench/reference/<block>.py``)."""


def read(rec):
    peak = rec.peaks.get("hbm_bytes_per_s")
    if rec.trace_window is None or not peak:
        return None
    lo, hi = rec.trace_window
    nbytes = sum(rec.block.prefill_bytes(rec.spec, n) if kind == "prefill"
                 else rec.block.decode_bytes(rec.spec, n)
                 for kind, n in rec.steps_in(lo, hi))
    if not nbytes:
        return None
    return 100.0 * nbytes / ((hi - lo) * peak)
