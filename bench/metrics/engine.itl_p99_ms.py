"""99th percentile over every gap between consecutive tokens of the
window's requests, as they reached the host: the first token at its
``first_token`` instant, each later one at the end of the engine's
``decode`` span whose ``rids`` holds the request (program spans).  None
when no ``decode`` span names its rows."""

import numpy as np

from bench.stats import pct


def read(run):
    if not run.events:
        return None
    mine = {r.req.rid for r in run.counted}
    times: dict[int, list[float]] = {}
    named = False
    for e in run.events:
        if e.etype == "first_token" and e.rid in mine:
            times.setdefault(e.rid, []).append(e.ts)
        elif e.etype == "decode" and "rids" in e.args:
            named = True
            for rid in e.args["rids"]:
                if rid in mine:
                    times.setdefault(rid, []).append(e.ts + e.dur)
    gaps = [g for t in times.values() for g in np.diff(sorted(t))]
    return pct((1e3 * g for g in gaps), 99) if named and gaps else None
