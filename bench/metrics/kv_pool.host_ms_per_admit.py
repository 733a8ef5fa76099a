"""Host time in the KV pool per admission of the window's requests: the
``pool_admit``, ``pool_register`` and ``pool_release`` spans of those
requests (each with its block-table upload), over their ``admit`` and
``resume`` events (program spans)."""

POOL_SPANS = ("pool_admit", "pool_register", "pool_release")


def read(run):
    if not run.events:
        return None
    mine = {r.req.rid for r in run.counted}
    secs, spans, admits = 0.0, 0, 0
    for e in run.events:
        if e.rid not in mine:
            continue
        if e.etype in POOL_SPANS:
            secs += e.dur
            spans += 1
        elif e.etype in ("admit", "resume"):
            admits += 1
    return 1e3 * secs / admits if spans and admits else None
