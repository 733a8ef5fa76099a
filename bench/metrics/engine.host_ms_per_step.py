"""Mean host time per engine step: each ``bench.step`` span around
``engine.step()`` less the device busy time inside it (profiler trace)."""


def read(run):
    s = run.trace["step_host_s"] if run.trace else []
    return 1e3 * sum(s) / len(s) if s else None
