"""95th percentile over the window's requests of due time to admission
into a slot (the engine's ``admit`` events)."""

from bench.stats import pct


def read(run):
    if not run.events:
        return None
    admit = {}
    for e in run.events:
        if e.etype == "admit" and e.rid not in admit:
            admit[e.rid] = e.ts
    return pct(((admit[r.req.rid] - r.due) * 1e3 for r in run.counted
                if r.req.rid in admit), 95)
