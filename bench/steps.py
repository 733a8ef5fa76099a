"""Each decode step's batch, rebuilt from the engine's lifecycle events.

The engine's tracer records, per request, ``admit`` (slot, prompt
length), ``first_token`` and ``finish``, and per batched decode step a
``decode`` span with its row count.  Between its first token and its end
a slot decodes at every step, so the attended length of a row at its
k-th decode step is its prompt length plus k.
"""

from __future__ import annotations

import numpy as np


def decode_contexts(events: list, lo: float, hi: float) -> list[list[int]]:
    """Attended lengths of the rows of every decode step that started in
    [lo, hi) (perf_counter seconds)."""
    dec = sorted(e.ts for e in events if e.etype == "decode")
    dec_ts = np.asarray(dec)
    admit, first, spans = {}, {}, []
    for e in sorted(events, key=lambda e: e.ts):
        if e.etype in ("admit", "resume"):
            admit[e.slot] = (e.rid, e.args["prompt_len"])
        elif e.etype == "first_token":
            first[e.slot] = e.ts
        elif e.etype == "finish" and e.slot in first and e.slot in admit:
            spans.append((first.pop(e.slot), e.ts, admit[e.slot][1]))
    for slot, t in first.items():           # still decoding at the end
        if slot in admit:
            spans.append((t, np.inf, admit[slot][1]))
    out = []
    for e in events:
        if e.etype != "decode" or not lo <= e.ts < hi:
            continue
        ctx = []
        for t_first, t_end, plen in spans:
            if t_first < e.ts < t_end:
                k = int(np.searchsorted(dec_ts, e.ts, "right")
                        - np.searchsorted(dec_ts, t_first, "right"))
                ctx.append(plen + k)
        if len(ctx) != e.args.get("rows", len(ctx)):
            raise ValueError(f"decode step at {e.ts}: rebuilt {len(ctx)} "
                             f"rows, the engine ran {e.args['rows']}")
        out.append(ctx)
    return out
