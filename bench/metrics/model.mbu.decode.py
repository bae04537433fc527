"""model.mbu.decode: HBM bytes that the decode steps need, over the time
they took, over the chip's peak HBM bandwidth, in percent.  A step's
time is the gap between its token and the one before it, as the harness
stamped them; the steps are those of the requests due in the window
that finished before the profiler started, since profiling slows the
service.  The bytes (weights once per step, K/V at the actual context)
are counted by the config's block (``bench/reference/<block>.py``).
Unlike ``model.mbu`` it does not count the time between requests, so
the offered rate does not set it."""

import math


def read(rec):
    peak = rec.peaks.get("hbm_bytes_per_s")
    cut = math.inf if rec.profile_started is None else rec.profile_started
    nbytes, seconds = 0, 0.0
    for r in rec.requests:
        t = r.token_times
        if r.due >= rec.seconds or not t or t[-1] >= cut:
            continue
        for i in range(1, len(t)):
            nbytes += rec.block.decode_bytes(rec.spec, r.prompt_len + i)
            seconds += t[i] - t[i - 1]
    if not peak or not seconds:
        return None
    return 100.0 * nbytes / (seconds * peak)
