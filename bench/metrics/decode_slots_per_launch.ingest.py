"""Decode slots served per decode launch in the ingest window, from the
engine's counters: ``slot_steps / decode_launches``."""


def read(ctx):
    e = ctx["counters"].get("engine", {})
    if not e.get("decode_launches"):
        return None
    return e["slot_steps"] / e["decode_launches"]
