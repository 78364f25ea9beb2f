"""LM tokens (prompt in plus summary out) per chunk inserted, from the
graph's update reports: the paper's token cost of an insert."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("chunks"):
        return None
    return (c["tokens_in"] + c["tokens_out"]) / c["chunks"]
