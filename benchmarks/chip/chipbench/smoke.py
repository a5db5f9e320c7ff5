"""Tiny versions of the benchmark's cells, for its CPU tests.

The same families, files and code paths as the cells, at the widths of the
program's smoke configurations (two layers, d_model 128), with short
prompts and outputs, so that a whole run fits in seconds on a CPU.
"""
from __future__ import annotations

import copy
import json

from chipbench.runner import BENCH_DIR, Cell


def conf(config: str) -> dict:
    c = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    if c["family"] == "mamba2":
        c.update(d_model=128, n_layer=2, vocab_size=500)
        c["mamba2_defaults"].update(d_state=16, headdim=32, chunk_size=32)
    else:
        c.update(hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=4, intermediate_size=256,
                 vocab_size=512)
    c["serve"].update(max_slots=4, max_len=512)
    c["prompt_vocab"] = 500
    return c


def traffic(name: str, rate: float = 2.0, backlog: int = 20) -> dict:
    t = json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())
    t = copy.deepcopy(t)
    for s in t["streams"]:
        s["prompt"].update(round_up=64, min=64, max=192)
        s["output"].update(max=40, max_total=300)
        if "rate" in s:
            s["rate"] = rate
        if "backlog" in s:
            s["backlog"] = backlog
    return t


def cell(config: str, traffic_name: str, limit: float = 1e-3,
         rate: float = 2.0) -> Cell:
    """A tiny cell; ``limit`` is the worst relative logit error that
    passes (the program on a CPU computes float32 as the reference does,
    to rounding)."""
    return Cell(name=f"{config}.{traffic_name}", config=config,
                conf=conf(config), traffic=traffic(traffic_name, rate),
                chips=1,
                end_to_end=[{"name": n, "unit": u} for n, u in (
                    ("itl_p50_ms", "ms"), ("itl_p98_ms", "ms"),
                    ("setup_s", "s"))],
                per_layer=[], limits={"logit_err": {"limit": limit}})
