"""Continuous-batching engine with real JAX execution.

This is the data plane of a serving instance: slot-based KV/state pool,
iteration-level scheduling (admit -> decode-one-token -> retire), preemption
of batch requests with host KV offload (Chiron's mixed-instance eviction),
and the ITL / throughput measurements the local autoscaler closes its loop
on. The max batch size is the knob Algorithm 1 turns.

The engine serves any architecture behind the unified ``Model`` API —
dense, MoE, SSM, hybrid, enc-dec, VLM — because caches are written/read
through the generic slot-pool protocol below.
"""
from __future__ import annotations

# mirror-sync: module ok(real engine has no RequestLedger/InstancePlane)
# The columnar mirrors exist only in the simulated data plane.
import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.models import Model
from repro.obs.host import span
from repro.serving.request import Request, RequestState, RequestType

_SCALAR_KEYS = ("pos",)
_ROW_KEYS = ("slot_pos",)


@functools.lru_cache(maxsize=None)
def prefill_program(cfg: ModelConfig, dtype) -> Callable:
    """The jitted prefill of one (config, dtype), built once per process
    and shared by every engine. ``jax.jit`` keeps one program per shape of
    its arguments: the prompt's length (and, on the chunked path, the past
    cache's), so an admission at a shape any engine has prefilled before
    costs no tracing, lowering or loading. Params, prompt batch and past
    cache are arguments, never constants of the program."""
    model = Model(cfg)

    def engine_prefill(params, batch, past_cache):
        return model.prefill(params, batch, dtype=dtype,
                             past_cache=past_cache)

    return jax.jit(engine_prefill)


def dot_is_one_bf16_pass(dot: Callable, device=None) -> bool:
    """Whether ``dot`` (float32 operands, committed to ``device``) computes
    what rounding both operands to bfloat16 and multiplying exactly
    computes. The operands are small integers scaled by 1 + 2**-10, a
    factor bfloat16 rounds away and float32 or TF32 keep; every product and
    sum of the rounded operands is exact in any order of accumulation."""
    i = np.arange(128 * 128)
    ints = [(1 + i[:8 * 128] % 8).reshape(8, 128),
            (1 + (3 * i + i // 128) % 8).reshape(128, 128)]
    x, w = (a.astype(np.float32) * np.float32(1 + 2 ** -10) for a in ints)
    got = np.asarray(dot(jax.device_put(x, device), jax.device_put(w, device)))
    xr, wr = (a.astype(jnp.bfloat16).astype(np.float64) for a in (x, w))
    return bool(np.array_equal(got, xr @ wr))


@functools.lru_cache(maxsize=None)
def _dot_probe(device, precision) -> bool:
    del precision        # a key of the cache: the setting the dot runs under
    return dot_is_one_bf16_pass(jnp.matmul, device)


def default_dot_is_bf16(device) -> bool:
    """Whether a float32 ``x @ w`` at the default matmul precision runs on
    ``device`` as one bfloat16 pass (so on the TPU, not on the CPU).
    Observed once per device and precision setting."""
    return _dot_probe(device, jax.config.jax_default_matmul_precision)


@dataclass
class StepStats:
    now: float
    n_active: int
    new_tokens: int
    finished: List[Request] = field(default_factory=list)
    itl: float = 0.0                 # seconds for this decode iteration
    throughput: float = 0.0          # tokens/s over the sliding window
    preempted: List[Request] = field(default_factory=list)


@dataclass
class _Slot:
    request: Optional[Request] = None
    # next input token: row ``row`` of ``token``, the prefill's argmax (1,)
    # or the last decode step's argmax over every slot (max_slots,)
    token: Optional[jax.Array] = None
    row: int = 0

    @property
    def active(self) -> bool:
        return self.request is not None


class Engine:
    def __init__(self, cfg: ModelConfig, *, key=None, params=None,
                 max_slots: int = 8, max_len: int = 256,
                 max_batch_size: Optional[int] = None,
                 clock=time.monotonic, dtype=jnp.float32,
                 prefix_cache_entries: int = 0,
                 prefill_chunk: int = 0,
                 device: Optional[jax.Device] = None,
                 obs=None, instance_id: int = -1):
        self.cfg = cfg
        # flight recorder (repro.obs), which RealCluster also hands over
        # when telemetry is armed, and the serving instance's id its span
        # rows carry; with None each span site costs a profiler annotation
        # and one branch
        self.obs, self.instance_id = obs, instance_id
        self.model = Model(cfg)
        self.dtype = dtype
        # the params copy and the slot pool live on ``device`` (the first
        # of jax.devices() when None); every step then runs there, since
        # the committed params pull the computation onto their device
        self.device = device or jax.devices()[0]
        key = key if key is not None else jax.random.PRNGKey(0)
        with jax.default_device(self.device):
            params = params if params is not None else \
                self.model.init(key, dtype=dtype)
            self.params = self._prepare(jax.device_put(params, self.device))
            self.pool = self.model.init_cache(max_slots, max_len, dtype=dtype)
        self.max_slots = max_slots
        self.max_len = max_len
        self.max_batch_size = max_batch_size or max_slots
        self.clock = clock
        # serving-optimization knobs (transformer family only; paper Fig.11)
        chunkable = cfg.arch_type in ("dense", "moe")
        self.prefill_chunk = prefill_chunk if chunkable else 0
        self.prefix_cache = None
        if prefix_cache_entries > 0 and chunkable:
            from repro.serving.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(prefix_cache_entries)
        self.slots: List[_Slot] = [_Slot() for _ in range(max_slots)]
        self.waiting: Deque[Request] = deque()
        # shardings given, so that a step's committed tokens and pool run
        # the program that a first call with uncommitted inputs lowered
        self._decode = jax.jit(self.model.decode_step,
                               in_shardings=SingleDeviceSharding(self.device))
        self._prefill_program = prefill_program(cfg, jnp.dtype(dtype))
        self._last_step_t: Optional[float] = None
        self._window: Deque = deque(maxlen=32)   # (t, tokens) samples
        self._rng = np.random.default_rng(0)

    def _prepare(self, params):
        """The params the decode and prefill programs take: where the
        device's float32 dot is one bfloat16 pass, the dot-only weights
        rounded to bfloat16 now, so no program converts them per call. The
        span's ``arg`` is the MiB so rounded (0 where nothing is)."""
        prep = functools.partial(self.model.serving_params,
                                 bf16_dots=default_dot_is_bf16(self.device))
        rounded = sum(
            a.size * a.dtype.itemsize for a, b in zip(
                jax.tree.leaves(params),
                jax.tree.leaves(jax.eval_shape(prep, params)))
            if a.dtype != b.dtype)
        with span("engine.prepare_weights", self.obs, self.instance_id,
                  arg=round(rounded / 2 ** 20)):
            return jax.block_until_ready(prep(params))

    # ------------------------------------------------------------ metrics
    @property
    def n_active(self) -> int:
        return sum(s.active for s in self.slots)

    @property
    def n_waiting(self) -> int:
        return len(self.waiting)

    def utilization(self) -> float:
        return self.n_active / max(self.max_batch_size, 1)

    def running_types(self) -> List[RequestType]:
        return [s.request.request_type for s in self.slots if s.active]

    def throughput(self) -> float:
        if len(self._window) < 2:
            return 0.0
        dt = self._window[-1][0] - self._window[0][0]
        toks = sum(t for _, t in list(self._window)[1:])
        return toks / dt if dt > 0 else 0.0

    # ------------------------------------------------------------ intake
    def submit(self, req: Request) -> None:
        req.state = RequestState.QUEUED
        self.waiting.append(req)

    def set_max_batch_size(self, b: int) -> None:
        self.max_batch_size = max(1, min(int(b), self.max_slots))

    # --------------------------------------------------------- slot cache
    def _write_slot(self, slot: int, sub: Dict[str, jax.Array]) -> None:
        """Write a batch-of-1 cache pytree into the pool at ``slot``."""
        for k, v in sub.items():
            if k in _SCALAR_KEYS:
                self.pool[k] = self.pool[k].at[slot].set(v[0])
            elif k in _ROW_KEYS:
                S = v.shape[1]
                row = jnp.full((self.max_len,), -1, v.dtype).at[:S].set(v[0])
                self.pool[k] = self.pool[k].at[slot].set(row)
            else:
                pool = self.pool[k]
                if v.ndim >= 3 and v.shape[2] != pool.shape[2]:
                    S = v.shape[2]
                    self.pool[k] = pool.at[:, slot, :S].set(v[:, 0])
                else:
                    self.pool[k] = pool.at[:, slot].set(v[:, 0])

    def _read_slot(self, slot: int) -> Dict[str, np.ndarray]:
        out = {}
        for k, v in self.pool.items():
            if k in _SCALAR_KEYS:
                out[k] = np.asarray(v[slot:slot + 1])
            elif k in _ROW_KEYS:
                out[k] = np.asarray(v[slot:slot + 1])
            else:
                out[k] = np.asarray(v[:, slot:slot + 1])
        return out

    def _restore_slot(self, slot: int, saved: Dict[str, np.ndarray]) -> None:
        for k, v in saved.items():
            arr = jnp.asarray(v)
            if k in _SCALAR_KEYS or k in _ROW_KEYS:
                self.pool[k] = self.pool[k].at[slot].set(arr[0])
            else:
                self.pool[k] = self.pool[k].at[:, slot].set(arr[:, 0])

    # ------------------------------------------------------------ admit
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if not s.active:
                return i
        return None

    def _prompt_tokens(self, req: Request) -> np.ndarray:
        if req.prompt_tokens is not None:
            return np.asarray(req.prompt_tokens, np.int32).reshape(-1)
        return self._rng.integers(0, self.cfg.vocab_size,
                                  size=(req.prompt_len,), dtype=np.int32)

    def _prompt_batch(self, req: Request, toks: Optional[np.ndarray] = None):
        toks = toks if toks is not None else self._prompt_tokens(req)
        batch = {"tokens": jnp.asarray(toks[None])}
        if self.cfg.arch_type == "audio":
            batch["frames"] = jnp.zeros((1, self.cfg.enc_seq, self.cfg.d_model),
                                        self.dtype)
        if self.cfg.arch_type == "vlm":
            batch["vision"] = jnp.zeros((1, self.cfg.n_vision_tokens,
                                         self.cfg.d_model), self.dtype)
        return batch

    def _prefill(self, req: Request):
        """Prefill a prompt, via the prefix cache and/or in chunks when
        those knobs are enabled; returns (last_logits, cache)."""
        toks = self._prompt_tokens(req)
        with span("engine.prefill", self.obs, self.instance_id, req.req_id,
                  len(toks)):
            past = None
            if self.prefix_cache is not None:
                past, consumed = self.prefix_cache.lookup(toks)
                remaining = toks[consumed:]
            else:
                remaining = toks
            chunk = self.prefill_chunk or len(remaining)
            logits = None
            for lo in range(0, len(remaining), chunk):
                piece = remaining[lo:lo + chunk]
                logits, past = self._prefill_program(
                    self.params, self._prompt_batch(req, piece), past)
            if self.prefix_cache is not None:
                self.prefix_cache.store(toks, past)
        return logits, past

    def _admit(self, req: Request, now: float) -> bool:
        slot = self._free_slot()
        if slot is None:
            return False
        obs, iid, rid = self.obs, self.instance_id, req.req_id
        with span("engine.admit", obs, iid, rid):
            if req.saved_kv is not None:
                with span("engine.restore", obs, iid, rid):
                    self._restore_slot(slot, req.saved_kv)
                req.saved_kv = None
                tok = jnp.zeros((1,), jnp.int32)
            else:
                logits, cache = self._prefill(req)
                with span("engine.slot_write", obs, iid, rid):
                    self._write_slot(slot, jax.tree.map(lambda a: a, cache))
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                req.tokens_generated += 1
                if req.first_token_time is None:
                    req.first_token_time = now
            req.state = RequestState.RUNNING
            self.slots[slot] = _Slot(req, tok)
        return True

    def preempt_one_batch(self, now: float) -> Optional[Request]:
        """Evict the most recently admitted batch request (KV to host)."""
        for i in reversed(range(self.max_slots)):
            s = self.slots[i]
            if s.active and s.request.request_type == RequestType.BATCH:
                req = s.request
                with span("engine.preempt", self.obs, self.instance_id,
                          req.req_id):
                    req.saved_kv = self._read_slot(i)
                req.state = RequestState.PREEMPTED
                req.preemptions += 1
                self.slots[i] = _Slot()
                return req
        return None

    # ------------------------------------------------------------ step
    def step(self) -> StepStats:
        if not self.waiting and not self.n_active:
            # nothing to serve: no spans, so an idle serving loop's
            # passes leave no rows and no profiler events
            now = self.clock()
            self._last_step_t = now
            return StepStats(now=now, n_active=0, new_tokens=0)
        obs, iid = self.obs, self.instance_id
        with span("engine.step", obs, iid):
            now = self.clock()
            stats = StepStats(now=now, n_active=0, new_tokens=0)

            # 1. admit (interactive first — zero-queuing), preempting batch
            #    requests on a full instance if an interactive request waits.
            with span("engine.schedule", obs, iid):
                self.waiting = deque(sorted(
                    self.waiting,
                    key=lambda r: (not r.is_interactive, r.arrival_time)))
                while self.waiting and self.n_active < self.max_batch_size:
                    req = self.waiting[0]
                    if not self._admit(req, now):
                        break
                    self.waiting.popleft()
                if self.waiting and self.waiting[0].is_interactive and \
                        self.n_active >= self.max_batch_size:
                    victim = self.preempt_one_batch(now)
                    if victim is not None:
                        stats.preempted.append(victim)
                        self._admit(self.waiting.popleft(), now)

            active_idx = [i for i, s in enumerate(self.slots) if s.active]
            stats.n_active = len(active_idx)
            if not active_idx:
                self._last_step_t = now
                return stats

            # 2. one decode iteration over the whole slot pool
            with span("engine.stack", obs, iid):
                tokens = jnp.stack([
                    s.token[s.row] if s.active else jnp.zeros((), jnp.int32)
                    for s in self.slots])[:, None]
            with span("engine.decode", obs, iid):
                logits, self.pool = self._decode(self.params, tokens,
                                                 self.pool)
                next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
            # dispatch returns before the device finishes: wait, so the ITL
            # the local autoscaler reads is the device step and not the
            # enqueue
            with span("engine.sync", obs, iid):
                next_tok.block_until_ready()
            t_end = self.clock()
            itl = (t_end - self._last_step_t) if self._last_step_t \
                else (t_end - now)
            self._last_step_t = t_end
            stats.itl = itl

            # 3. bookkeeping: ITL samples, finishes. The positions come to
            # the host in one read, and a continuing slot keeps its row of
            # next_tok, so the host's work here issues no device op per
            # active slot and a token gap does not grow with their number
            with span("engine.retire", obs, iid):
                pos = np.asarray(self.pool["pos"])
                for i in active_idx:
                    s = self.slots[i]
                    req = s.request
                    req.itl_samples.append(itl)
                    req.tokens_generated += 1
                    stats.new_tokens += 1
                    if req.first_token_time is None:
                        req.first_token_time = t_end
                    if req.tokens_generated >= req.output_len or \
                            pos[i] >= self.max_len - 1:
                        req.state = RequestState.FINISHED
                        req.finish_time = t_end
                        stats.finished.append(req)
                        self.slots[i] = _Slot()
                    else:
                        s.token, s.row = next_tok, i

                self._window.append((t_end, stats.new_tokens))
                stats.throughput = self.throughput()
        return stats
