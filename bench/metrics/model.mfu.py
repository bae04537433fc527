"""model.mfu: FLOPs that serving needs for the model steps whose tokens
arrived in the traced window, over the window's seconds times the
chip's bf16 peak, in percent.  The FLOPs are counted from the config's
shapes by its block (``bench/reference/<block>.py``)."""


def read(rec):
    peak = rec.peaks.get("bf16_flops_per_s")
    if rec.trace_window is None or not peak:
        return None
    lo, hi = rec.trace_window
    flops = sum(rec.block.prefill_flops(rec.spec, n) if kind == "prefill"
                else rec.block.decode_flops(rec.spec, n)
                for kind, n in rec.steps_in(lo, hi))
    if not flops:
        return None
    return 100.0 * flops / ((hi - lo) * peak)
