"""model step: the prefill's share of the chip's peak. Operations the
prefill algorithm needs (``chipbench.flops``, from each admitted prompt's
length) over the device-busy time inside the ``chipbench.prefill`` spans
(the traced run waits for the device before and after each prefill, so
that time is the prefill's own), over the peak (``chipbench.peaks``).
Percent."""
from chipbench import flops
from chipbench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    spans = sorted(s for s in ctx.trace.spans if s[2] == "chipbench.prefill")
    done = ctx.rec.prefills
    if not spans or len(spans) != len(done):
        return None
    count = flops.PREFILL[ctx.cell.conf["family"]]
    devices = {i: d for i, d in enumerate(ctx.trace.devices)}
    ops, busy = 0, 0.0
    for (s, e, _), (eng, *_, n) in zip(spans, done):
        dev = devices.get(ctx.rec.devices.get(eng, 0))
        if dev is None:
            return None
        busy += tr.busy(dev.ops, s, e)
        ops += count(ctx.dims, n)
    if busy <= 0:
        return None
    return 100.0 * ops / busy / ctx.peaks["flops_per_s"]
