"""kernel: the Pallas ``ssd_scan`` (``kernels/ssd_scan.py``) against its
roofline. For every prefill in the window the kernel runs once per layer
over the prompt padded to a whole chunk; the least time each run could
take is the larger of its operations over the peak and its bytes over the
bandwidth (``chipbench.flops.ssd_scan``, from shapes). The share is that
least time, summed, over the kernel's device time in the trace. Percent."""
from chipbench import flops
from chipbench import trace as tr

KERNEL = "ssd_scan"     # its custom call's instruction name in the trace


def kernel_seconds(ctx):
    lo, hi = ctx.trace.window
    return sum(e - s for d in ctx.trace.devices for s, e, n in d.ops
               if tr.instruction(n).split(".")[0] == KERNEL
               and lo <= s and e <= hi)


def read(ctx):
    if ctx.trace is None:
        return None
    spent = kernel_seconds(ctx)
    if spent <= 0:
        return None
    m, pk = ctx.dims, ctx.peaks
    least = 0.0
    for *_, n in ctx.rec.prefills:
        ops, nbytes = flops.ssd_scan(n, m["H"], m["P"], m["N"], m["chunk"])
        least += m["L"] * max(ops / pk["flops_per_s"],
                              nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / spent
