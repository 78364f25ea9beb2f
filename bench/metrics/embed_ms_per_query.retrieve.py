"""Host milliseconds the query embedder took per question in the
retrieval window.  The embedder is host NumPy, so the host clock
around its calls times it soundly."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("queries"):
        return None
    return 1e3 * c["embed_s"] / c["queries"]
