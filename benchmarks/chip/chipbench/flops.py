"""Operations and bytes the algorithms need, computed from shapes.

These are the benchmark's own counts, not the compiler's: padding, masked
work and recomputation are not counted. Matmul operations count 2 per
multiply-add. Bytes are float32 (4 bytes) reads and writes of HBM that the
algorithm cannot avoid.
"""
from __future__ import annotations

import math

F32 = 4


def ssd_scan(s: int, h: int, p: int, n: int, chunk: int) -> tuple:
    """(operations, bytes) of one chunked SSD scan over ``s`` positions of
    one sequence: per chunk the C B^T scores once (B and C are shared by the
    heads of a group), and per head the intra-chunk product with x, the
    inter-chunk output C h^T and the state update x^T (B * decay). The
    kernel pads ``s`` up to a whole chunk; the padding is not counted."""
    nc = math.ceil(s / chunk)
    ops = nc * 2 * chunk * chunk * n + s * h * (2 * chunk * p + 4 * n * p)
    bytes_ = F32 * (2 * s * h * p + s * h + 2 * s * n + 2 * h * p * n)
    return ops, bytes_


def mamba2_prefill(m: dict, s: int) -> int:
    """Operations of a Mamba2 prefill of ``s`` tokens: projections, conv and
    SSD in every layer, and the output head at the last position only."""
    d, di, N, H, P, W = (m[k] for k in ("d", "di", "N", "H", "P", "W"))
    proj = 2 * s * d * (2 * di + 2 * N + H) + 2 * s * di * d
    conv = 2 * W * s * (di + 2 * N)
    ssd, _ = ssd_scan(s, H, P, N, m["chunk"])
    return m["L"] * (proj + conv + ssd) + 2 * d * m["V"]


def olmo_prefill(m: dict, s: int) -> int:
    """Operations of an OLMo prefill of ``s`` tokens: q, k, v, o and the
    SwiGLU MLP in every layer, causal attention (half of the s x s scores
    and of their product with v), and the output head at the last
    position only."""
    d, F = m["d"], m["F"]
    dense = 2 * s * d * 4 * d + 2 * s * d * F * 3
    attn = 2 * s * s * d
    return m["L"] * (dense + attn) + 2 * d * m["V"]


PREFILL = {"mamba2": mamba2_prefill, "olmo": olmo_prefill}
