"""Batched autoregressive serving engine over packed MX-family weights.

The engine owns:
  * a packed parameter tree (``repro.serve.prequant`` / checkpoint load) —
    every GEMM weight resident in HBM as the codec-tagged u8 streams of
    ``cfg.quant_format`` (any ``repro.core.codecs`` entry with an encoder:
    m2xfp at 4.5 bits/element, mxfp4, nvfp4, ...), decoded inline by the
    quantized matmul (codec Pallas kernel on TPU, XLA decode mirror
    otherwise — see repro.models.quant);
  * a paged KV cache: ``init_caches(..., per_slot=True)`` — batch row b is
    request slot b, a fixed-size page of the cache pool with its own
    position track, admitted/evicted independently (continuous batching);
    with ``cfg.kv_quant`` set to a KV-capable codec pages hold its packed
    streams (m2xfp: Sg-EM codes/scales/meta);
  * a host-side ``SlotScheduler`` deciding which request occupies which
    slot each step and how many tokens each slot consumes.

Every step runs ONE jitted launch over all slots. Slots in the decode
phase consume one token each; newly admitted requests **prefill in chunks**
of up to ``prefill_chunk`` prompt tokens per step through
``repro.models.model.prefill_chunk`` — the packed weight streams cross HBM
once per chunk instead of once per prompt token, which is what makes
time-to-first-token scale with ``ceil(prompt / chunk)`` instead of
``prompt``. A mixed step (some slots prefilling, some decoding) is a single
``prefill_chunk`` launch with a per-slot chunk-length vector: decode slots
carry length 1, idle slots length 0 (masked out of every cache write). When
every planned length is 1 the engine uses the plain ``decode_step`` launch.
Both paths are bit-identical per token — pinned by tests/test_serve.py.

The scheduler's ``plan_chunks`` token-budget policy caps total prefill
tokens per step so a long prompt cannot starve decoding neighbours.
Slots whose request finished keep ticking on a dummy token until the
scheduler refills them; admit-time reset invalidates the slot's position
track (which masks every stale KV entry) and re-initializes recurrent
state, so no state leaks between requests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import model as _model
from repro.models.model import decode_step, init_caches

from . import guard as _guard
from .guard import (EngineFailedError, EngineGuard, GuardConfig,
                    TransientStepError)
from .scheduler import AdmissionError, Request, SlotScheduler

# TTFT is quantized in engine steps; buckets cover 1..256-step prompts
_TTFT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
# the parts of a step's host time ServeStats.note_step keeps apart
_SPLIT = ("dispatch_s", "wait_s", "guard_s")

__all__ = ["ServeEngine", "ServeStats", "tree_nbytes"]


def tree_nbytes(tree) -> int:
    """Total bytes of every array leaf (what the tree keeps resident)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "dtype"))


@dataclasses.dataclass
class ServeStats:
    n_slots: int = 1
    steps: int = 0                 # launches (decode or mixed prefill)
    decode_steps: int = 0          # pure one-token launches
    prefill_steps: int = 0         # launches that carried prefill chunks
    slot_steps: int = 0            # sum over steps of slots making progress
    prefill_tokens: int = 0        # prompt tokens fed (excl. sampling step)
    generated_tokens: int = 0      # tokens sampled and returned
    wall_s: float = 0.0
    prefill_wall_s: float = 0.0    # wall attributed to prefill launches
    decode_wall_s: float = 0.0     # wall attributed to pure decode launches
    quarantined: int = 0           # requests evicted for poisoned state
    expired: int = 0               # requests past their deadline
    shed: int = 0                  # requests rejected at admission
    # where each step's host time went (always on: a few clock reads a step)
    dispatch_s: float = 0.0        # enqueueing launches (the jitted call)
    wait_s: float = 0.0            # blocked on the logits' copy to the host
    guard_s: float = 0.0           # the guard's drain and fault containment
    longest_step: Optional[dict] = None    # see note_step

    @property
    def tokens_per_sec(self) -> float:
        total = self.prefill_tokens + self.generated_tokens
        return total / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def prefill_tokens_per_sec(self) -> float:
        if self.prefill_wall_s <= 0:
            return 0.0
        return self.prefill_tokens / self.prefill_wall_s

    @property
    def decode_tokens_per_sec(self) -> float:
        if self.decode_wall_s <= 0:
            return 0.0
        return self.generated_tokens / self.decode_wall_s

    @property
    def occupancy(self) -> float:
        """Mean fraction of slots doing useful work per step."""
        if not self.steps:
            return 0.0
        return self.slot_steps / (self.steps * self.n_slots)

    def note_step(self, wall_s: float, dispatch_s: float, wait_s: float,
                  guard_s: float) -> None:
        """Add one step's split to the totals, and keep it as
        ``longest_step`` (``wall_s``, ``dispatch_s``, ``wait_s``,
        ``guard_s`` and the ``rest_s`` of admission, planning, sampling
        and routing) when no step since it was last ``None`` took
        longer."""
        self.dispatch_s += dispatch_s
        self.wait_s += wait_s
        self.guard_s += guard_s
        if self.longest_step is None or wall_s > self.longest_step["wall_s"]:
            self.longest_step = {
                "wall_s": wall_s, "dispatch_s": dispatch_s,
                "wait_s": wait_s, "guard_s": guard_s,
                "rest_s": wall_s - dispatch_s - wait_s - guard_s}

    def to_dict(self) -> dict:
        """Every field plus every derived property, as plain floats/ints —
        what benches and the obs JSONL sink serialize (no poking at
        dataclass internals)."""
        out = dataclasses.asdict(self)
        out.update(
            tokens_per_sec=self.tokens_per_sec,
            prefill_tokens_per_sec=self.prefill_tokens_per_sec,
            decode_tokens_per_sec=self.decode_tokens_per_sec,
            occupancy=self.occupancy,
        )
        return out


def _greedy(logits: np.ndarray) -> np.ndarray:
    """(B, V) -> (B,) argmax token ids."""
    return np.argmax(logits, axis=-1).astype(np.int32)


def _reset_slot(caches: dict, slot: jax.Array, scrub: bool = False) -> dict:
    """Return ``caches`` with one slot's rows back in their init state.

    Every cache leaf is layer-stacked with the slot (batch) axis second.
    For a normal admit-time reset attention K/V pages need no scrub —
    setting the slot's position track to -1 masks every stale entry
    (``attention_decode``'s valid test), so only the position rows and the
    recurrent-state rows are written. ``m`` is the mlstm/slstm running
    log-max, initialized to -1e30.

    ``scrub=True`` (quarantine path) additionally zeroes the slot's K/V
    pages and packed-KV streams: a poisoned page (NaN float, reserved
    scale byte 255) would re-trip the KV sentinel every subsequent step if
    left masked-but-resident. Zero is the init state of every page stream
    (packed-KV scale byte 0 = empty page)."""
    def fix(path, leaf):
        keys = [str(getattr(p, "key", "")) for p in path]
        name = keys[-1] if keys else ""
        if name == "pos":
            return leaf.at[:, slot].set(-1)
        if any(k in ("mlstm", "slstm", "mamba") for k in keys):
            fill = -1e30 if name == "m" else 0.0
            return leaf.at[:, slot].set(jnp.asarray(fill, leaf.dtype))
        if scrub:
            return leaf.at[:, slot].set(jnp.zeros((), leaf.dtype))
        return leaf                        # K/V pages: masked via pos
    return jax.tree_util.tree_map_with_path(fix, caches)


class ServeEngine:
    """Continuous-batching decode engine. See module docstring.

    Parameters
    ----------
    params : packed parameter tree (``prequantize_params`` output) — or a
        dense tree if ``cfg.quant != 'serve'`` (useful for A/B parity runs).
    cfg : ModelConfig, normally with ``quant='serve'``.
    n_slots : batch width = number of concurrently served requests.
    max_len : cache capacity per slot (prompt + generated tokens; a
        sliding-window config bounds the page at the window instead).
    sample_fn : (B, V) float32 logits -> (B,) int32 token ids; greedy
        argmax by default (deterministic — what the parity tests pin).
    prefill_chunk : max prompt tokens consumed per slot per step. 1
        recovers the legacy one-token teacher forcing (and is forced for
        the recurrent ssm/hybrid families, whose per-token state updates
        cannot batch along the sequence).
    prefill_budget : cap on total prefill tokens per step across all slots
        (None = unlimited) so prefill-heavy traffic cannot starve decoding
        slots; the oldest prefilling request always progresses.
    guard : fault-tolerance config (``repro.serve.guard.GuardConfig``).
        None (default) = guard on with default knobs: NaN/poison sentinels
        traced into the launches, poisoned-slot quarantine, transient-step
        retries, health state machine. ``False`` = guard fully off — the
        launch graphs are byte-identical to the pre-guard engine.
    max_queue : bound on the admission queue (None = unbounded); a full
        queue sheds submissions with ``AdmissionError`` (backpressure).
    default_ttl_steps : deadline in engine steps applied to every request
        that does not carry its own ``ttl_steps`` (None = no deadline).
    verify_weights : run codec stream validation over the packed params at
        init, repairing broken leaves (re-quantize from ``source_params``
        when given, else clamp scales — see guard.verify_packed_tree).
    source_params : optional dense parameter tree enabling exact
        re-quantization repair of corrupt packed leaves.
    """

    def __init__(self, params, cfg, n_slots: int = 8, max_len: int = 256,
                 sample_fn: Optional[Callable] = None,
                 prefill_chunk: int = 8,
                 prefill_budget: Optional[int] = None,
                 guard=None, max_queue: Optional[int] = None,
                 default_ttl_steps: Optional[int] = None,
                 verify_weights: bool = False, source_params=None):
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.sample_fn = sample_fn or _greedy
        self.chunk = max(1, int(prefill_chunk))
        if cfg.family in ("ssm", "hybrid"):
            self.chunk = 1           # recurrent state updates token by token
        self.prefill_budget = prefill_budget
        if guard is False:
            gcfg = None
        else:
            gcfg = guard if isinstance(guard, GuardConfig) else GuardConfig()
        self.guard: Optional[EngineGuard] = \
            EngineGuard(gcfg) if gcfg is not None else None
        self.default_ttl_steps = default_ttl_steps
        self.source_params = source_params
        # sliding-window configs accept prompts longer than the page
        self.scheduler = SlotScheduler(
            n_slots, max_queue=max_queue,
            max_prompt_len=None if cfg.sliding_window else max_len)
        self.stats = ServeStats(n_slots=n_slots)
        self._split = dict.fromkeys(_SPLIT, 0.0)   # this step's host time

        if verify_weights:
            self.params, repairs = _guard.verify_packed_tree(
                params, cfg=cfg, source_params=source_params)
            if repairs and self.guard:
                # clamped leaves decode degraded (bounded error) — say so
                if any(mode == "clamp" for _, mode in repairs):
                    self.guard.degrade()

        self.caches = init_caches(cfg, n_slots, max_len, per_slot=True)
        # host-side per-slot state
        self._tokens = np.zeros((n_slots, 1), np.int32)   # last sampled token
        self._index = np.zeros((n_slots,), np.int32)      # absolute position

        # donate the cache pool: decode updates it in place instead of
        # materializing a second copy every step (2x HBM otherwise; CPU
        # ignores donation with a harmless warning). With the guard on, the
        # poison sentinels are traced into the same launch (per-slot
        # reductions + debug callback; numerics untouched — the golden-token
        # tests pin that).
        mailbox = self.guard.mailbox if self.guard else None
        nan_checks = bool(gcfg and gcfg.nan_checks)
        kv_checks = bool(gcfg and gcfg.kv_checks)

        def decode_fn(p, b, c, i):
            logits, c2 = decode_step(p, cfg, b, c, i)
            if nan_checks:
                # decode rows always attend over >= 1 valid entry (the
                # token just written), so no masking is needed
                _guard.probe_logits(mailbox, logits[:, -1])
            if kv_checks:
                _guard.probe_kv(mailbox, c2, n_slots)
            return logits, c2

        def prefill_fn(p, b, c, i, l):
            logits, c2 = _model.prefill_chunk(p, cfg, b, c, i, l)
            if nan_checks:
                # probe only the row each slot samples from; idle rows
                # (l == 0) legitimately softmax over an all-masked window
                rows = logits[jnp.arange(logits.shape[0]),
                              jnp.maximum(l - 1, 0)]
                _guard.probe_logits(mailbox, rows, lengths=l)
            if kv_checks:
                _guard.probe_kv(mailbox, c2, n_slots)
            return logits, c2

        self._sentinels_on = nan_checks or kv_checks
        self._step = jax.jit(decode_fn, donate_argnums=(2,))
        self._prefill = jax.jit(prefill_fn, donate_argnums=(2,))
        self._reset = jax.jit(_reset_slot, donate_argnums=(0,))
        self._scrub = jax.jit(lambda c, s: _reset_slot(c, s, scrub=True),
                              donate_argnums=(0,))

        # quantization-health sweep of the packed weights: per-layer clip
        # rate / scale saturation / meta modes / re-encode drift gauges,
        # once at startup (off the decode hot path)
        if obs.enabled("health"):
            with obs.span("serve.weight_health", cat="obs"):
                obs.quant_health.weight_tree_health(params)

    # -- request lifecycle -------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_id: Optional[int] = None,
               ttl_steps: Optional[int] = None) -> Request:
        """Queue a request; it is admitted when a slot frees up.

        Raises ``ValueError`` on an invalid request (empty prompt,
        non-positive ``max_new_tokens``, prompt over the cache page),
        :class:`AdmissionError` when the queue is full (backpressure —
        counted as shed), :class:`EngineFailedError` once the engine's
        fault budget is exhausted."""
        if self.guard:
            self.guard.check_alive()
        if prompt and len(prompt) + max_new_tokens > self.max_len \
                and not self.cfg.sliding_window:
            raise ValueError(
                f"prompt+generation {len(prompt)}+{max_new_tokens} exceeds "
                f"cache capacity {self.max_len}")
        if ttl_steps is None:
            ttl_steps = self.default_ttl_steps
        try:
            return self.scheduler.submit(
                list(prompt), max_new_tokens, eos_id,
                ttl_steps=ttl_steps, step=self.stats.steps)
        except AdmissionError as e:
            self.stats.shed += 1
            if self.guard:
                self.guard.record_shed(e.reason)
            raise

    def _admit(self) -> None:
        admitted = self.scheduler.admit(self.stats.steps)
        for req in admitted:
            slot = req.slot
            self.caches = self._reset(self.caches, jnp.int32(slot))
            self._index[slot] = 0
        if admitted and self.guard and self.guard.maybe_verify_admit():
            self._spot_check_weights()

    def _spot_check_weights(self) -> None:
        """verify_on_admit sampling: validate one random packed leaf's
        streams against its codec invariants; on damage, repair the whole
        tree (re-quantize from source when available, else clamp)."""
        from repro.core.codecs import PackedTensor, validate_packed
        leaves = [l for l in jax.tree.leaves(
            self.params, is_leaf=lambda x: isinstance(x, PackedTensor))
            if isinstance(l, PackedTensor)]
        if not leaves:
            return
        pick = int(self.guard._rng.integers(len(leaves)))
        if not validate_packed(leaves[pick]):
            return
        if obs.enabled():
            obs.counter("repro_guard_stream_invalid_total",
                        "packed leaves failing codec stream validation"
                        ).inc(stage="admit")
        self.params, _ = _guard.verify_packed_tree(
            self.params, cfg=self.cfg, source_params=self.source_params)
        self.guard.degrade()

    # -- decode loop -------------------------------------------------------

    def _launch_decode(self, chunks) -> np.ndarray:
        """One-token launch for every slot. Returns (B, V) f32 logits at
        each slot's (single) position."""
        for slot, req in self.scheduler.active.items():
            if req.phase == "prefill":
                self._tokens[slot, 0] = req.prompt[req.consumed]
        t0 = time.perf_counter()
        with obs.span("serve.launch.dispatch", kind="decode_step",
                      slots=self.n_slots):
            logits, self.caches = self._step(
                self.params, {"tokens": jnp.asarray(self._tokens)},
                self.caches, jnp.asarray(self._index))
        t1 = time.perf_counter()
        with obs.span("serve.launch.wait"):
            out = np.asarray(logits[:, -1]).astype(np.float32)
        self._split["dispatch_s"] += t1 - t0
        self._split["wait_s"] += time.perf_counter() - t1
        return out

    def _launch_prefill(self, chunks) -> np.ndarray:
        """Mixed chunked launch: prefilling slots consume their planned
        chunk, decode slots their next token, idle / budget-starved slots
        are masked out (length 0). Returns (B, V) f32 logits at each slot's
        last valid position."""
        toks = np.zeros((self.n_slots, self.chunk), np.int32)
        lens = np.zeros((self.n_slots,), np.int32)
        for slot, req in self.scheduler.active.items():
            c = chunks.get(slot, 0)
            if c == 0:
                continue
            lens[slot] = c
            if req.phase == "prefill":
                toks[slot, :c] = req.prompt[req.consumed:req.consumed + c]
            else:
                toks[slot, 0] = self._tokens[slot, 0]
        t0 = time.perf_counter()
        with obs.span("serve.launch.dispatch", kind="prefill_chunk",
                      slots=self.n_slots, tokens=int(lens.sum())):
            logits, self.caches = self._prefill(
                self.params, {"tokens": jnp.asarray(toks)}, self.caches,
                jnp.asarray(self._index), jnp.asarray(lens))
        t1 = time.perf_counter()
        with obs.span("serve.launch.wait"):
            lg = np.asarray(logits).astype(np.float32)    # (B, T, V)
        self._split["dispatch_s"] += t1 - t0
        self._split["wait_s"] += time.perf_counter() - t1
        return lg[np.arange(self.n_slots), np.maximum(lens - 1, 0)]

    def step(self) -> int:
        """Admit, plan per-slot chunks, run one batched launch, route
        tokens. Returns the number of requests that finished this step.

        Raises :class:`EngineFailedError` once the guard's fault budget is
        exhausted (FAILED state — transient failures persisted past the
        retry budget, or quarantines blew ``max_quarantines``)."""
        if self.guard:
            self.guard.check_alive()
        self._split = dict.fromkeys(_SPLIT, 0.0)
        t0 = time.perf_counter()
        with obs.span("serve.step", step=self.stats.steps):
            finished = self._step_inner()
        self.stats.note_step(time.perf_counter() - t0, **self._split)
        return finished

    def _guarded_launch(self, fn, chunks) -> np.ndarray:
        """Run a launch with the guard's transient-failure retry policy.
        Only :class:`TransientStepError` is retried — it is raised *before*
        the jitted call consumes its donated buffers, so re-running is
        safe. Anything else propagates."""
        if not self.guard:
            return fn(chunks)
        attempts = 0
        while True:
            try:
                return fn(chunks)
            except TransientStepError as e:
                if attempts >= self.guard.cfg.max_step_retries:
                    self.guard.fail(
                        f"transient step failure persisted after "
                        f"{attempts} retries: {e}")
                    raise EngineFailedError(
                        f"launch failed {attempts + 1} times "
                        f"({e}); engine is FAILED") from e
                self.guard.record_retry()
                time.sleep(self.guard.cfg.retry_backoff_s * (2 ** attempts))
                attempts += 1

    def _expire_deadlines(self) -> None:
        for req in self.scheduler.expire(self.stats.steps):
            self.stats.expired += 1
            where = ("running" if req.fail_reason == "deadline_running"
                     else "queued")
            if self.guard:
                self.guard.record_expired(where)
            obs.instant("serve.expire", rid=req.rid, where=where)

    def _contain_faults(self, chunks, rows: np.ndarray) -> None:
        """Poisoned-slot containment, between launch and token routing.

        Unions the in-jit sentinel counts (drained via effects_barrier)
        with a host-side non-finite scan of the sampled rows, then for
        every flagged slot: quarantine its request (if occupied), scrub
        its cache rows to init state, and mask it out of this step's
        routing. The other slots' rows are untouched — their tokens stay
        bit-identical to a fault-free run (batch-row independence)."""
        faults = self.guard.drain() if self._sentinels_on else {}
        poisoned = {}                              # slot -> first bad site
        kv = faults.get("kv")
        if kv is not None:
            for slot in np.nonzero(np.asarray(kv))[0]:
                poisoned[int(slot)] = "kv"
        lg = faults.get("logits")
        if lg is not None:
            for slot in np.nonzero(np.asarray(lg))[0]:
                if chunks.get(int(slot), 0) > 0:
                    poisoned.setdefault(int(slot), "logits")
        # host-side belt and braces (also covers guard configs that turned
        # the in-jit probes off)
        for slot in np.nonzero(~np.isfinite(rows).all(axis=-1))[0]:
            if chunks.get(int(slot), 0) > 0:
                poisoned.setdefault(int(slot), "logits")
        for slot, site in sorted(poisoned.items()):
            occupied = slot in self.scheduler.active
            self.caches = self._scrub(self.caches, jnp.int32(slot))
            self._index[slot] = 0
            self._tokens[slot, 0] = 0
            chunks[slot] = 0                       # no routing this step
            if occupied:
                req = self.scheduler.quarantine(
                    slot, self.stats.steps, reason=site)
                self.stats.quarantined += 1
                self.guard.record_quarantine(site)
                obs.instant("serve.quarantine", rid=req.rid, slot=slot,
                            site=site)
            else:
                self.guard.record_scrub(site)

    def _step_inner(self) -> int:
        self._expire_deadlines()
        with obs.span("serve.admit"):
            self._admit()
        if not self.scheduler.active:
            return 0
        with obs.span("serve.plan"):
            chunks = self.scheduler.plan_chunks(self.chunk,
                                                self.prefill_budget)
        decode_only = all(c == 1 for c in chunks.values())
        phase = "decode" if decode_only else "prefill"
        t0 = time.perf_counter()
        with obs.span(f"serve.phase.{phase}",
                      slots=len(self.scheduler.active)):
            launch = self._launch_decode if decode_only \
                else self._launch_prefill
            sampled_from = self._guarded_launch(launch, chunks)
        dt = time.perf_counter() - t0
        if self.guard:
            t1 = time.perf_counter()
            with obs.span("serve.guard.drain"):
                self._contain_faults(chunks, sampled_from)
            self._split["guard_s"] += time.perf_counter() - t1
        with obs.span("serve.sample"):
            sampled = self.sample_fn(sampled_from)

        finished = 0
        first_tokens, new_prefill, new_generated = [], 0, 0
        self.stats.steps += 1
        if decode_only:
            self.stats.decode_steps += 1
            self.stats.decode_wall_s += dt
        else:
            self.stats.prefill_steps += 1
            self.stats.prefill_wall_s += dt
        for slot, req in list(self.scheduler.active.items()):
            c = chunks.get(slot, 0)
            if c == 0:
                continue                       # budget-starved: no progress
            self.stats.slot_steps += 1
            if req.phase == "prefill":
                req.consumed += c
                still_prefilling = req.consumed < len(req.prompt)
                fed = c - (0 if still_prefilling else 1)
                self.stats.prefill_tokens += fed
                new_prefill += fed
                if still_prefilling:
                    self._index[slot] += c
                    continue                   # logits discarded
                # the chunk ended on the last prompt token: its logits
                # sample the first generated token
                self.stats.generated_tokens += 1
            else:
                self.stats.generated_tokens += 1
            new_generated += 1
            tok = int(sampled[slot])
            req.output.append(tok)
            if req.first_token_step < 0:
                req.first_token_step = self.stats.steps
                first_tokens.append(req)
            self._tokens[slot, 0] = tok
            self._index[slot] += c
            if req.done:
                self.scheduler.evict(slot, self.stats.steps)
                obs.instant("serve.evict", rid=req.rid)
                finished += 1
        if self.guard:
            self.guard.note_step(dt)
        if obs.enabled():
            self._record_step_metrics(phase, dt, first_tokens,
                                      new_prefill, new_generated, finished)
        return finished

    def _record_step_metrics(self, phase, dt, first_tokens, new_prefill,
                             new_generated, finished) -> None:
        obs.histogram("repro_serve_step_latency_seconds",
                      "wall seconds per engine launch").observe(
            dt, phase=phase)
        obs.counter("repro_serve_steps_total",
                    "engine launches").inc(phase=phase)
        if new_prefill:
            obs.counter("repro_serve_tokens_total",
                        "tokens through the engine").inc(
                new_prefill, kind="prefill")
        if new_generated:
            obs.counter("repro_serve_tokens_total", "").inc(
                new_generated, kind="generated")
        if finished:
            obs.counter("repro_serve_requests_finished_total",
                        "requests that completed").inc(finished)
        for req in first_tokens:
            obs.histogram("repro_serve_ttft_steps",
                          "engine steps from admission to first token",
                          buckets=_TTFT_BUCKETS).observe(req.ttft_steps)
        obs.gauge("repro_serve_queue_depth",
                  "requests waiting for a slot").set(
            len(self.scheduler.queue))
        obs.gauge("repro_serve_active_slots",
                  "slots holding a running request").set(
            len(self.scheduler.active))
        obs.gauge("repro_serve_occupancy",
                  "mean fraction of slots progressing per step").set(
            self.stats.occupancy)

    def run(self) -> List[Request]:
        """Step until queue and slots drain. Returns the requests that
        finished during *this* drain, in submission order."""
        already_done = len(self.scheduler.finished)
        t0 = time.perf_counter()
        with obs.span("serve.run", slots=self.n_slots):
            while self.scheduler.has_work:
                self.step()
        self.stats.wall_s += time.perf_counter() - t0
        obs.autodump()          # metrics.jsonl + trace.json -> REPRO_OBS_DIR
        return sorted(self.scheduler.finished[already_done:],
                      key=lambda r: r.rid)

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int,
                 eos_id: Optional[int] = None) -> List[List[int]]:
        """Batch convenience: submit every prompt, drain, return outputs."""
        reqs = [self.submit(p, max_new_tokens, eos_id) for p in prompts]
        self.run()
        return [r.output for r in reqs]

    # -- accounting --------------------------------------------------------

    @property
    def health(self) -> str:
        """Current health state ('healthy' when the guard is off)."""
        return self.guard.state if self.guard else _guard.HEALTHY

    def guard_summary(self) -> dict:
        """Fault-accounting snapshot (state, quarantines, retries, ...);
        empty dict when the guard is off."""
        return self.guard.summary() if self.guard else {}

    def mean_ttft_steps(self) -> float:
        """Mean steps from admission to first sampled token over every
        request that produced output (chunked prefill drives this down from
        ~prompt_len to ~ceil(prompt_len / prefill_chunk))."""
        ttfts = [r.ttft_steps for r in self.scheduler.finished
                 if r.ttft_steps >= 0]
        ttfts += [r.ttft_steps for r in self.scheduler.active.values()
                  if r.ttft_steps >= 0]
        return float(np.mean(ttfts)) if ttfts else 0.0

    def weight_bytes(self) -> int:
        return tree_nbytes(self.params)

    def kv_bytes(self) -> int:
        return tree_nbytes(self.caches)
