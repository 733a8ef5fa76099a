"""The paged-decode kernel's share of its roofline: per decode step, the
least time for its valid blocks' bytes and FLOPs at the chip's peaks,
over the kernel's device time per step (profiler trace)."""

from bench import costs
from bench.steps import decode_contexts
from bench.trace import modules_matching, ops_matching

PROGRAM = "decode_sample_paged"
KERNEL = "paged_decode"


def read(run):
    if not run.trace or not run.events:
        return None
    k_secs, k_n = ops_matching(run.trace, KERNEL)
    _, n = modules_matching(run.trace, PROGRAM)
    steps = decode_contexts(run.events, run.window.start, run.window.end)
    if not k_n or not n or not steps:
        return None
    least = [costs.roofline_s(*costs.paged_decode_kernel(
        run.m, c, run.cell["block_size"]), run.peaks)[0] for c in steps]
    return 100.0 * (sum(least) / len(least)) / (k_secs / n)
