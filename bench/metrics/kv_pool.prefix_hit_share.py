"""Share of the window's admitted prompt tokens whose KV blocks came from
the prefix cache (``shared_tokens`` of the engine's ``admit`` events)."""


def read(run):
    if not run.events:
        return None
    mine = {r.req.rid for r in run.counted}
    shared = total = 0
    for e in run.events:
        if e.etype == "admit" and e.rid in mine:
            shared += e.args["shared_tokens"]
            total += e.args["prompt_len"]
    return shared / total if total else None
