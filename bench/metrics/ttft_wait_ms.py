"""The part of a request's time to first token that is not its own
prefill, in ms: the median, over requests whose first token came in the
window, of that time less the request's ``prefill`` span. It holds the
wait in the queue before the prefill and the first token held back
while the rest of its step (other prefills, the batch's decode, the
host's bookkeeping) runs, since the first token reaches the client when
the step returns.

A request's time to first token is the harness's (submission to the
return of the step that held its prefill). The step that held it is the
window's engine ``step`` span of the same index as the harness's step;
its ``prefill`` spans go to its first-tokened requests in admission
(submission) order. A step whose counts differ is left out."""

import numpy as np


def read(ctx):
    steps = sorted((ts, dur) for ts, dur, name in ctx.spans
                   if name == "step")
    prefills = sorted((ts, dur) for ts, dur, name in ctx.spans
                      if name == "prefill")
    if not steps or len(steps) != len(ctx.steps) or not prefills:
        return None
    pf_t0 = np.asarray([ts for ts, _ in prefills], np.int64)
    first = {}
    for r in ctx.requests:
        if r.times and r.submit is not None:
            first.setdefault(r.times[0], []).append(r)
    waits = []
    for (ts, dur), st in zip(steps, ctx.steps):
        reqs = sorted(first.get(st.t1, []), key=lambda r: (r.submit, r.index))
        lo, hi = np.searchsorted(pf_t0, [ts, ts + dur])
        if not reqs or hi - lo != len(reqs):
            continue
        for r, (_, pf_dur) in zip(reqs, prefills[lo:hi]):
            waits.append(r.times[0] - r.submit - pf_dur * 1e-9)
    return float(np.median(waits) * 1e3) if waits else None
