"""Host time the synchronous engine loop adds to every step, in ms: the
mean over the window's ``step`` spans of the span less its
``prefill.sync`` and ``decode.sync`` children (the waits for the device's
tokens). None for a program without those spans."""

import numpy as np


def read(ctx):
    steps = sorted((ts, dur) for ts, dur, name in ctx.spans
                   if name == "step")
    syncs = [(ts, dur) for ts, dur, name in ctx.spans
             if name in ("prefill.sync", "decode.sync")]
    if not steps or not syncs:
        return None
    t0 = np.asarray([ts for ts, _ in steps], np.int64)
    self_ns = np.asarray([dur for _, dur in steps], np.float64)
    for ts, dur in syncs:
        i = int(np.searchsorted(t0, ts, side="right")) - 1
        if i >= 0 and ts + dur <= t0[i] + steps[i][1]:
            self_ns[i] -= dur
    return float(self_ns.mean() * 1e-6)
