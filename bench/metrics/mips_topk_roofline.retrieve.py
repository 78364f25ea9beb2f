"""Roofline share of the exact scan in the retrieval window, in
percent: the least time the chip needs for each query block (the
larger of its operations over the bfloat16 peak and its bytes over the
HBM peak, from the scanned buffer's shape; ``bench/flops/mips_topk``)
summed over the window's blocks, over the device time of the jitted
``_mips_topk`` programs in the trace.  The scan reads the store's
buffer in place, so at the store's shapes the program is the kernel
alone; a launch that pads the buffer (the program counter
``kernels.mips_topk.padded_launches``) would add its pad here.  At
these shapes bytes bound it."""
from bench.flops.mips_topk import least_seconds
from bench.trace import time_by_name


def read(ctx):
    c, red = ctx["counters"], ctx["trace"]
    device_s = time_by_name(red.all_modules(),
                            lambda n: "mips_topk" in n) / 1e9
    if device_s <= 0 or not c.get("sizes"):
        return None
    least = sum(least_seconds(b, c["rows"], c["cols"], c["k"],
                              ctx["peaks"])[0] for b in c["sizes"])
    return 100.0 * least / device_s
