"""Output tokens emitted inside the window, over the window (host clock).

A request's tokens are placed evenly between its first token and its end
(the engine stamps no single token), and those that fall inside the
window count, whichever request they belong to."""

import numpy as np


def read(run):
    lo, hi = run.window.start, run.window.end
    n = 0.0
    for r in run.window.records:
        if r.status != "ok" or r.tokens is None:
            continue
        k = len(r.tokens)
        t = r.first + (np.arange(k) * ((r.finish - r.first) / (k - 1))
                       if k > 1 else np.zeros(1))
        n += int(np.count_nonzero((t >= lo) & (t < hi)))
    return n / (hi - lo)
