"""The whole decode step's share of the chip's peak: the least time the
chip could take for the step -- the larger of its FLOPs over the bf16 peak
and its bytes (every weight once, plus the valid KV) over the HBM peak --
over the decode program's device time, averaged over the window's steps.
Rows and their lengths come from the engine's lifecycle events."""

from bench import costs
from bench.steps import decode_contexts
from bench.trace import modules_matching

PROGRAM = "decode_sample_paged"


def read(run):
    if not run.trace or not run.events:
        return None
    secs, n = modules_matching(run.trace, PROGRAM)
    steps = decode_contexts(run.events, run.window.start, run.window.end)
    if not n or not steps:
        return None
    least = [costs.roofline_s(*costs.decode_step(run.m, c), run.peaks)[0]
             for c in steps]
    return 100.0 * (sum(least) / len(least)) / (secs / n)
