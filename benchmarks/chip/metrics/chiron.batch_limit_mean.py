"""controller: the batch limit Chiron's local autoscaler (Algorithm 1) sets
on each instance, as the engine applies it (``Engine.max_batch_size``,
clamped to the slots), recorded at each loop pass where it changed and
averaged over the window's time and the instances. Slots."""


def read(ctx):
    pts = [(t, sum(v) / len(v)) for t, v in ctx.rec.limits
           if v and t < ctx.clock.end]
    if not pts:
        return None
    pts.append((ctx.clock.end, pts[-1][1]))
    area = sum((t1 - t0) * v for (t0, v), (t1, _) in zip(pts, pts[1:]))
    return area / (pts[-1][0] - pts[0][0])
