"""99th percentile of how late the load generator submitted each of the
window's requests after its due time (host clock)."""

from bench.stats import pct


def read(run):
    return pct(((r.submit - r.due) * 1e3 for r in run.counted
                if r.submit), 99)
