"""Analytic per-instance performance model (roofline-calibrated).

The paper measures ITL/throughput-vs-batch-size on A100s (Fig. 3); we
re-derive the same trade-off for the TPU-v5e target from first principles
(DESIGN.md §3 hardware adaptation):

  decode step time(b) = max(compute, memory) + collective + overhead
    memory   = (weight_bytes + kv_bytes(b)) / (chips * HBM_bw)
    compute  = 2 * N_active * b / (chips * peak_flops)
    collective = 2 * d_model * bytes * (tp-1)/tp * n_layers / link_bw  (TP allreduce)

  preemption: when the resident KV demand exceeds the pool, evicted
  requests must re-prefill; each re-prefill steals decode time, inflating
  ITL and bending throughput DOWN past an inflection point — the exact
  phenomenon Chiron's TBP metric detects (paper Fig. 3).

All constants are module-level and overridable for calibration tests.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

from repro.configs import get_config
from repro.configs.base import ModelConfig

# TPU v5e-class chip (task-given constants)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
HBM_BYTES = 16e9             # per chip
ICI_BW = 50e9                # bytes/s per link
BYTES_PER_PARAM = 2          # bf16 weights
STEP_OVERHEAD = 2e-3         # dispatch/sampling overhead per decode step
MFU_DECODE = 0.6             # achievable fraction of peak in decode GEMMs
MBU = 0.75                   # achievable HBM bandwidth fraction

# default tensor-parallel instance sizes (chips per serving instance)
INSTANCE_CHIPS: Dict[str, int] = {
    "llama-8b": 4, "llama-70b": 16,
    "olmo-1b": 1, "granite-8b": 4, "zamba2-2.7b": 2, "phi3-mini-3.8b": 2,
    "yi-34b": 8, "mamba2-1.3b": 1, "qwen2-moe-a2.7b": 4,
    "deepseek-moe-16b": 8, "whisper-base": 1, "internvl2-2b": 2,
}

# model-load times (paper: 15 s – 1 min; scaled with checkpoint size)
_LOAD_BW = 2e9               # bytes/s host->HBM per chip during model load


@dataclass
class PerfModel:
    """Latency/throughput/memory responses for one (model, instance) pair.

    Accelerator variants (heterogeneous fleets): the ``*_scale`` fields
    derate or boost the v5e-class baseline constants per chip generation —
    a fleet cluster built on a faster part passes ``flops_scale`` /
    ``hbm_bw_scale`` / ``hbm_bytes_scale`` > 1 and every latency, capacity,
    and throughput response shifts coherently (see
    ``repro.sim.fleet.ACCELERATORS``).
    """
    model_name: str
    chips: int = 0
    cfg: ModelConfig = None
    # optimization knobs that shift the optimum the local autoscaler finds
    # (paper Fig. 11): prefix caching preloads KV; spec decode adds draft work
    prefix_caching: bool = False
    speculative_decoding: bool = False
    prefix_hit_tokens: int = 512
    spec_draft_overhead: float = 0.15
    spec_accept_speedup: float = 2.0
    # accelerator-generation scaling vs the v5e-class baseline constants
    flops_scale: float = 1.0
    hbm_bw_scale: float = 1.0
    hbm_bytes_scale: float = 1.0

    def __post_init__(self):
        self.cfg = self.cfg or get_config(self.model_name)
        self.chips = self.chips or INSTANCE_CHIPS.get(self.model_name, 4)
        self.n_params = self.cfg.param_count()
        self.n_active = self.cfg.active_param_count()
        self.weight_bytes = self.n_params * BYTES_PER_PARAM
        # The hot-path responses (itl / can_admit) run millions of times per
        # simulation; fold every shape-derived constant once.
        self._kv_per_tok = self._kv_bytes_per_token()
        free = self.chips * HBM_BYTES * self.hbm_bytes_scale \
            - self.weight_bytes
        self._kv_cap = float("inf") if self._kv_per_tok <= 0 else \
            max(free, 0) * 0.9 / self._kv_per_tok   # 10% activation headroom
        mem_bw = self.chips * HBM_BW * self.hbm_bw_scale * MBU
        self._flops_per_s = self.chips * PEAK_FLOPS * self.flops_scale \
            * MFU_DECODE
        self._mem_t_base = self.weight_bytes / mem_bw
        self._mem_t_per_kvtok = self._kv_per_tok / mem_bw
        self._comp_t_per_seq = 2 * self.n_active / self._flops_per_s
        self._coll_t = 0.0
        if self.chips > 1:
            coll_bytes = 2 * self.cfg.d_model * BYTES_PER_PARAM * \
                self.cfg.n_layers * (self.chips - 1) / self.chips
            self._coll_t = coll_bytes / ICI_BW

    # ------------------------------------------------------------ memory
    def _kv_bytes_per_token(self) -> float:
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        if cfg.arch_type == "ssm":
            return 0.0  # O(1) state, amortized to ~0 per token
        return 2 * cfg.n_attention_layers * cfg.n_kv_heads * hd * \
            BYTES_PER_PARAM

    def kv_bytes_per_token(self) -> float:
        return self._kv_per_tok

    def kv_capacity_tokens(self) -> float:
        return self._kv_cap

    # ------------------------------------------------------------ latency
    def prefill_time(self, prompt_len: int) -> float:
        eff_len = prompt_len
        if self.prefix_caching:
            eff_len = max(prompt_len - self.prefix_hit_tokens, 16)
        flops = 2 * self.n_active * eff_len
        return flops / self._flops_per_s + STEP_OVERHEAD

    def itl(self, batch_size: int, mean_ctx: float = 1024.0) -> float:
        """Inter-token latency at a given running batch size."""
        b = max(batch_size, 1)
        mem_t = self._mem_t_base + b * mean_ctx * self._mem_t_per_kvtok
        comp_t = b * self._comp_t_per_seq
        t = max(mem_t, comp_t) + self._coll_t + STEP_OVERHEAD
        if self.speculative_decoding:
            t = t * (1 + self.spec_draft_overhead * math.sqrt(b)) \
                / self.spec_accept_speedup
        # preemption inflation past the KV-capacity inflection point
        t *= self.preemption_factor(b, mean_ctx)
        return t

    def preemption_factor(self, batch_size: int, mean_ctx: float) -> float:
        """ITL multiplier from eviction/re-prefill past KV capacity."""
        cap = self.kv_capacity_tokens()
        if not math.isfinite(cap):
            return 1.0
        eff_ctx = mean_ctx
        if self.prefix_caching:
            eff_ctx = mean_ctx + self.prefix_hit_tokens  # preloaded prefix KV
        demand = batch_size * eff_ctx
        if demand <= cap:
            return 1.0
        over = demand / cap - 1.0
        # each over-capacity fraction triggers re-prefills worth ~ctx tokens
        return 1.0 + 4.0 * over + 8.0 * over * over

    def throughput(self, batch_size: int, mean_ctx: float = 1024.0) -> float:
        """Aggregate decode tokens/s at a given batch size."""
        return batch_size / self.itl(batch_size, mean_ctx)

    def max_stable_batch(self, mean_ctx: float = 1024.0) -> int:
        return int(self.kv_capacity_tokens() / max(mean_ctx, 1))

    # ------------------------------------------------------------ scaling
    def model_load_time(self) -> float:
        return max(15.0, min(self.weight_bytes / (self.chips * _LOAD_BW), 60.0))

    def optimal_batch(self, itl_slo: float, mean_ctx: float = 1024.0,
                      max_batch: int = 4096) -> int:
        """Largest batch meeting the ITL SLO without throughput regression —
        the fixed point Algorithm 1 converges to (used by tests/benches)."""
        best, best_b = 0.0, 1
        for b in range(1, max_batch + 1):
            t = self.itl(b, mean_ctx)
            thr = b / t
            if t > itl_slo:
                break
            if thr <= best:
                break
            best, best_b = thr, b
        return best_b
