"""kernel: the Pallas ``flash_prefill`` (``kernels/flash_prefill.py``)
against its roofline. For every prefill in the window the kernel runs once
per attention layer over the whole prompt; the least time each run could
take is the larger of its operations over the peak and its bytes over the
bandwidth (``ops_and_bytes``, from shapes). The share is that least time,
summed, over the kernel's device time in the trace. Percent."""
from chipbench import trace as tr

KERNEL = "flash_prefill"   # its custom call's instruction name in the trace
F32 = 4


def ops_and_bytes(s: int, h: int, h_kv: int, d: int) -> tuple:
    """(operations, bytes) of one causal attention over ``s`` positions of
    one sequence, ``h`` query heads over ``h_kv`` K/V heads of ``d``: the
    lower triangle of Q K^T and of the product of its softmax with V,
    S^2 H D multiply-adds each (2 S^2 H D operations in all); float32 Q, K,
    V read and the output written once."""
    ops = 2 * s * s * h * d
    nbytes = F32 * s * d * (2 * h + 2 * h_kv)
    return ops, nbytes


def kernel_seconds(ctx):
    lo, hi = ctx.trace.window
    return sum(e - s for d in ctx.trace.devices for s, e, n in d.ops
               if tr.instruction(n).split(".")[0] == KERNEL
               and lo <= s and e <= hi)


def read(ctx):
    if ctx.trace is None:
        return None
    spent = kernel_seconds(ctx)
    m, pk = ctx.dims, ctx.peaks
    if spent <= 0 or not m.get("n_attn"):
        return None
    least = 0.0
    for *_, n in ctx.rec.prefills:
        ops, nbytes = ops_and_bytes(n, m["Hq"], m["Hkv"], m["hd"])
        least += m["n_attn"] * max(ops / pk["flops_per_s"],
                                   nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / spent
