"""Mean over the window's working ``engine.step()`` calls of the host's own
time: each ``step`` span less the ``sync`` spans inside it (the same
``tick``), in which the host waits for the device (program spans)."""


def read(run):
    if not run.events:
        return None
    sync: dict[int, float] = {}
    steps = []
    for e in run.events:
        if e.etype == "sync":
            sync[e.tick] = sync.get(e.tick, 0.0) + e.dur
        elif e.etype == "step" and run.window.start <= e.ts < run.window.end:
            steps.append(e)
    s = [e.dur - sync.get(e.tick, 0.0) for e in steps]
    return 1e3 * sum(s) / len(s) if s else None
