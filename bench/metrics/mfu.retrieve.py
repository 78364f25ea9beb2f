"""The retrieval window's share of the chip's bfloat16 peak, in
percent: the exact scan's operations over every query block (the only
arithmetic of the path that runs on the chip, counted from the
scanned buffer's shape by ``bench/flops/mips_topk``) over the traced
window.  It bounds the scan's roofline share from the whole window's
side: a change that takes the scan off the path leaves the roofline
silent but not this."""
from bench.flops.mips_topk import ops


def read(ctx):
    c, red = ctx["counters"], ctx["trace"]
    if not c.get("sizes") or red.window_s <= 0:
        return None
    total = sum(ops(b, c["rows"], c["cols"]) for b in c["sizes"])
    return 100.0 * total / (red.window_s * ctx["peaks"]["bf16_flops_per_s"])
