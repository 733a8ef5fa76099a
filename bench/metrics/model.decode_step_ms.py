"""Device time of the decode program per execution (profiler trace)."""

from bench.trace import modules_matching

PROGRAM = "decode_sample_paged"


def read(run):
    if not run.trace:
        return None
    secs, n = modules_matching(run.trace, PROGRAM)
    return 1e3 * secs / n if n else None
