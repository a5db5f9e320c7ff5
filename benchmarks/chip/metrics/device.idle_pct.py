"""device: share of the traced window in which no operation ran on the
chip (1 - union of the ``XLA Ops`` intervals over the window), averaged
over the cell's chips. Percent."""
from chipbench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.trace.window
    busy = [tr.busy(d.ops, lo, hi) for d in ctx.trace.devices]
    if not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
