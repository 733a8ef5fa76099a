"""95th percentile over the window's requests of (finish - first token) /
(tokens - 1) (host clock).  A failed request counts as never served."""

from bench.stats import pct, tpot_ms


def read(run):
    return pct((v for r in run.counted if (v := tpot_ms(r)) is not None), 95)
