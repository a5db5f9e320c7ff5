"""engine: one admission (``Engine._admit``: prefill, first token, slot
write), median over the window. In the traced run the benchmark's wrapper
waits for the device at its end, so the span holds the device work.
Milliseconds."""
import statistics


def read(ctx):
    spans = [t1 - t0 for _, t0, t1 in ctx.rec.admits if t1 <= ctx.clock.end]
    return 1e3 * statistics.median(spans) if spans else None
