"""Model FLOP utilisation of the traced window: the operations of the
real, unpadded prompt and generated tokens the LM served in it (counted
by the cell from the published widths, through the architecture's
``bench/flops/<model_type>.py``), over the traced window times the
chip's bfloat16 peak, in percent."""


def read(ctx):
    c, red = ctx["counters"], ctx["trace"]
    if not c.get("lm_flops") or red.window_s <= 0:
        return None
    return 100.0 * c["lm_flops"] / (red.window_s
                                    * ctx["peaks"]["bf16_flops_per_s"])
