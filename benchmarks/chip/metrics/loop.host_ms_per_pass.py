"""cluster loop: host time of one ``serve_forever`` pass outside the
engines' ``step`` (arrivals, controller, routing, accounting), median over
the window's passes in which some engine decoded. Milliseconds."""
import bisect
import statistics


def read(ctx):
    passes = [t for t in ctx.clock.passes if t <= ctx.clock.end]
    starts = [s[1] for s in ctx.rec.steps]
    out = []
    for a, b in zip(passes, passes[1:]):
        steps = ctx.rec.steps[bisect.bisect_left(starts, a):
                              bisect.bisect_left(starts, b)]
        if any(n for *_, n in steps):
            out.append((b - a) - sum(t1 - t0 for _, t0, t1, _ in steps))
    return 1e3 * statistics.median(out) if out else None
