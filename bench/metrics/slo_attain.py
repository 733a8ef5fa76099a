"""Share of the window's requests that met both of the mix's limits (time
to first token and time per output token).  A failed request misses."""

from bench.stats import tpot_ms, ttft_ms


def read(run):
    if not run.counted:
        return None
    lim = run.mix["limits"]
    ok = 0
    for r in run.counted:
        tp = tpot_ms(r)
        ok += (ttft_ms(r) <= lim["ttft_ms"]
               and (tp is None or tp <= lim["tpot_ms"]))
    return ok / len(run.counted)
