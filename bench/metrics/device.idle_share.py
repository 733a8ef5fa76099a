"""1 - device busy time / traced window: busy is the union of the
intervals in which an operation ran on the device (profiler trace)."""


def read(run):
    if not run.trace:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
