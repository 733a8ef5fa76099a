"""Preemptions in the window (``engine.preemptions``) per request of the
window that finished."""


def read(run):
    done = sum(1 for r in run.counted if r.status == "ok")
    if not done:
        return None
    return run.counters.get("engine.preemptions", 0) / done
