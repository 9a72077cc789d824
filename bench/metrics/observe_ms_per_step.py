"""Host time of the engine's ``observe`` span (estimator, re-plan,
controller) per engine step, in ms."""

import numpy as np


def read(ctx):
    d = [dur for _, dur, name in ctx.spans if name == "observe"]
    return float(np.mean(d) * 1e-6) if d else None
