"""Model FLOP utilisation of the ingest window: the operations of the
real, unpadded prompt and generated tokens the LM served (counted by
``bench/flops/qwen2.py`` from the published widths), over the traced
window times the chip's bfloat16 peak, in percent."""
from bench.flops.qwen2 import total_flops


def read(ctx):
    c, red = ctx["counters"], ctx["trace"]
    if not c.get("served") or red.window_s <= 0:
        return None
    ops = total_flops(c["shape"], c["served"])
    return 100.0 * ops / (red.window_s * ctx["peaks"]["bf16_flops_per_s"])
