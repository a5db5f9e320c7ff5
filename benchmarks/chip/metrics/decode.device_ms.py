"""model step: device time of one run of the jitted decode step
(``Model.decode_step`` under ``Engine._decode``), median over the window
and the chips, from the ``XLA Modules`` line of the trace. Milliseconds."""
import statistics


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window
    runs = [e - s for d in ctx.trace.devices for s, e, n in d.modules
            if "decode_step" in n and lo <= s and e <= hi]
    return 1e3 * statistics.median(runs) if runs else None
