"""95th percentile over the window's requests of due time to first token
(host clock).  A failed request counts as never served."""

from bench.stats import pct, ttft_ms


def read(run):
    return pct((ttft_ms(r) for r in run.counted), 95)
