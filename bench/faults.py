"""Faults planted under the timed path, to show that ``correct`` catches
them. The benchmark's own runs never plant one; ``control.py --fault``
and ``tests/test_correctness.py`` do.

Each fault takes the warm engine and wraps its jitted decode step so that
its inputs or outputs change on the host: no new program compiles, and
the harness's compile count stays as it is.
"""

import numpy as np


class Wrapped:
    """A jitted step with its inputs or outputs altered on the host; keeps
    the jit's cache-size probe."""

    def __init__(self, fn, pre=None, post=None):
        self.fn, self.pre, self.post = fn, pre, post

    def __call__(self, *a):
        a = self.pre(a) if self.pre else a
        out = self.fn(*a)
        return self.post(a, out) if self.post else out

    def _cache_size(self):
        return self.fn._cache_size()


def state_unchanged(eng):
    """Decode returns the KV pool it was given: no decoded token's K, V."""
    eng._decode_fn = Wrapped(eng._decode_fn,
                             post=lambda a, o: (o[0], o[1], a[2], o[3]))


def token_altered(eng):
    """Slot 0's token is replaced where decode produces it."""
    V = eng.cfg.vocab_size

    def post(a, o):
        t = np.asarray(o[0]).copy()
        t[0, 0] = (t[0, 0] + 1) % V
        return (t,) + tuple(o[1:])
    eng._decode_fn = Wrapped(eng._decode_fn, post=post)


def half_batch(eng):
    """The upper half of the decode batch attends over no context."""
    def pre(a):
        lens = np.asarray(a[4]).copy()
        lens[len(lens) // 2:] = 0
        return a[:4] + (lens,) + a[5:]
    eng._decode_fn = Wrapped(eng._decode_fn, pre=pre)


FAULTS = {"state_unchanged": state_unchanged, "token_altered": token_altered,
          "half_batch": half_batch}
