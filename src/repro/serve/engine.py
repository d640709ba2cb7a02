"""Batched serving engines: static rounds + continuous batching.

Two schedulers share the decode path (DESIGN.md §6/§9):

  * :class:`ServeEngine` — static batching rounds.  Requests queue in; each
    *round* admits up to ``n_slots`` requests with equal prompt length (the
    queue is grouped by length), prefills them in lockstep (exact w.r.t.
    the cache), then generates greedily until every admitted request hits
    its token budget.  Rounds are independent: the cache is re-initialized
    per round, so no state leaks between requests.  This engine stays
    deliberately simple — it is the *differential-testing oracle* the
    continuous engine is fuzzed against (DESIGN.md §9).

  * :class:`ContinuousEngine` — continuous batching.  The KV cache is
    slot-indexed with per-slot position counters and per-slot attention
    masks (models.init_cache(per_slot=True)), so slots at different
    sequence offsets decode in ONE lockstep dispatch.  Finished slots are
    evicted and refilled mid-flight from the queue: an admission burst is
    co-prefilled over its common prefix via ``decode_chunk`` (bit-exact vs
    the per-token path), ragged tails finish per-row, and each row is
    grafted into its free slot with ``models.cache_write_slot`` while the
    other slots keep their state.  No equal-length grouping, no
    head-of-line blocking, no idle slots waiting for the longest request
    in a round.

Prefill has two modes (DESIGN.md §8):

  * per-token (``prefill_chunk=None``) — one ``decode_step`` dispatch per
    prompt token, the reference semantics;
  * chunked (``prefill_chunk=C``) — ``models.decode_chunk`` steps the cache
    C tokens per device call (a lax.scan whose body IS decode_step, so the
    logits and cache are bit-exact vs the per-token path), cutting prompt
    dispatch count from O(prompt_len) to ceil(prompt_len/C).  Each distinct
    chunk shape jits once; a prompt costs at most two shapes (full chunks +
    one remainder).

Requests carry arrival timestamps; both engines stamp first-token and
finish times, so ``Request.ttft_s`` / ``Request.tpot_s`` give per-request
time-to-first-token and time-per-output-token — the latency axes
benchmarks/serve_bench.py reports p50/p99 over.  Per-round timing hooks
land in ``engine.round_stats`` (static) / ``engine.step_stats``
(continuous); ``prefill_s`` is device wall-clock up to the last prefill
logits being ready — the pick of the first token is decode-side.  The
continuous engine picks every token on the device (``serve_greedy``) and
copies only the int32 ids to the host; the static engine keeps its host
argmax, so the differential tests hold the device pick against numpy's.

Observability (DESIGN.md §11): the engines open ``repro.obs`` spans
around their host work.  While a JAX profiler session collects, every
span is a ``TraceAnnotation`` on the profiler's clock, so a device trace
can charge its idle gaps to the span the host was in.  The continuous
engine's tree is ``serve.step`` ⊃ ``serve.admit`` (one burst) ⊃
{``serve.admit.prefill``, ``.tail``, ``.graft``, ``.first_token``} and
``serve.step`` ⊃ ``serve.decode`` ⊃ {``.dispatch``, ``.wait``, ``.sync``,
``.commit``}; the static engine opens ``serve.prefill`` and
``serve.decode`` per round.  The jitted programs are named
(``serve_decode_step``, ``serve_prefill_chunk``, ``serve_greedy``,
``serve_init_cache``, ``serve_admit_row``, ``cache_write_slot``,
``cache_reset_slot``), so each device op's module says which program it
belongs to.  When ``repro.obs`` is enabled the same spans become Chrome
events that adopt the SAME perf_counter stamps that back
RoundStats/StepStats/Request (per-row admission spans on slot-numbered
lanes), request lifecycle lands as trace instants
(``serve.request.arrival`` / ``first_token`` / ``finish``) plus
``repro_serve_ttft_seconds`` / ``repro_serve_tpot_seconds`` histograms,
queue depth and slot occupancy are gauges, and admissions/evictions/tokens
are counters.  With obs disabled and no profiler session every hook is a
no-op behind one check: token streams and stats are byte-identical either
way (asserted in tests/test_obs_integration.py).

Resilience (DESIGN.md §12): both engines accept an optional
``resilience=ResilienceConfig(...)`` enabling per-request deadlines with
cancellation (monotonic-clock expiry — immune to the chaos clock-skew
fault), bounded admission queues with load shedding, transient-dispatch
retry-with-backoff (``dist.fault.RestartPolicy``), payload-integrity
checksums with exact healing (``serve.resilience.PayloadGuard``),
queue-pressure degradation down the serving bit ladder
(``DegradePolicy`` hot-swaps the param tree at step boundaries — the KV
cache is format-independent, so in-flight slots continue), and periodic
engine snapshots through ``dist.checkpoint`` (``ContinuousEngine.resume``
rebuilds a bit-identical engine).  Every fault-handling action emits obs
events; with ``resilience=None`` (default) each branch is one ``is
None`` test and behavior is byte-identical to before.  The chaos hooks
(``repro.chaos``) sit at serve.step/serve.admit/serve.decode (continuous)
and serve.round (static), each behind one ``chaos.enabled()`` check, and
always fire BEFORE the engine mutates state for that step — so a retried
dispatch replays identically and recovered token streams stay
bit-identical to the fault-free run (the chaos-smoke CI matrix).

Weights may be served dequantized-on-the-fly from WaterSIC int codes
(quant/qlinear) — the paper's deployment story: decode is weight-bytes
bound, so 2–4 bit codes cut the dominant roofline term; the packed-int4
leaf format halves the weight bytes again vs int8, the int3 bit-plane
leaf takes 3/8 of them.  Mixed-rate param trees (repro.plan, DESIGN.md
§10) serve directly: models.layers.dense dispatches per leaf, so a 3-bit
MLP stack and an 8-bit output projection coexist in one engine — both
engines record the realized ``weight_bytes`` and per-format
``weight_formats`` histogram at construction so benchmarks and drivers
report the mix next to tokens/s.  launch/serve.py wraps the same
decode_step in pjit for the production mesh.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import chaos, obs
from repro.configs.base import ArchConfig
from repro.models import (cache_reset_slot, cache_write_slot, decode_chunk,
                          decode_step, init_cache)
from repro.quant import leaf_format_histogram, qweight_bytes
from repro.serve.config import EngineConfig, resolve_engine_config
from repro.serve.resilience import (EngineStalledError, PayloadGuard,
                                    ResilienceConfig)

__all__ = ["Request", "RoundStats", "StepStats", "ServeEngine",
           "ContinuousEngine", "EngineStalledError", "EngineConfig",
           "ResilienceConfig"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # latency accounting (perf_counter seconds; stamped by the engines)
    arrival_s: Optional[float] = None      # set by submit() if unset
    admitted_s: Optional[float] = None     # start of the admitting burst
    first_token_s: Optional[float] = None  # first output token materialized
    finish_s: Optional[float] = None       # budget filled
    # resilience (DESIGN.md §12)
    deadline_s: Optional[float] = None     # seconds from arrival; expiry is
                                           # measured on the MONOTONIC clock
    arrival_mono: Optional[float] = None   # monotonic arrival (deadline base)
    dropped: bool = False                  # shed or deadline-expired
    drop_reason: Optional[str] = None      # "shed-queue-full" | "deadline"

    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first token: queue wait + prefill + the first pick (on
        the device in the continuous engine, a host argmax in the static
        one) and its ids to the host."""
        if self.arrival_s is None or self.first_token_s is None:
            return None
        return self.first_token_s - self.arrival_s

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time per output token after the first (None if < 2 tokens)."""
        if self.first_token_s is None or self.finish_s is None \
                or len(self.out_tokens) < 2:
            return None
        return (self.finish_s - self.first_token_s) \
            / (len(self.out_tokens) - 1)


@dataclasses.dataclass
class RoundStats:
    """Wall-clock + dispatch accounting for one static-batching round."""

    batch: int
    prompt_len: int
    prefill_calls: int               # device dispatches spent on the prompt
    prefill_s: float                 # up to last prefill logits ready (the
                                     # first token's pick is decode-side)
    decode_calls: int                # generation decode dispatches
    decode_s: float
    new_tokens: int                  # tokens emitted across the batch
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    tpot_s: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepStats:
    """One continuous-batching scheduler step (DESIGN.md §9)."""

    active: int                      # slots decoding this step
    admitted: int                    # requests admitted before the dispatch
    finished: int                    # requests evicted after the dispatch
    new_tokens: int                  # tokens emitted (admission + decode)
    step_s: float                    # wall clock of the whole step


def _run_prefill(decode_fn, decode_chunk_fn, params, cache,
                 prompts: np.ndarray, chunk: Optional[int]):
    """Feed the prompt through the cache; returns (logits, cache, calls).

    Chunked mode issues ceil(plen/chunk) decode_chunk dispatches (each a
    scanned run of decode_step — bit-exact vs per-token); per-token mode
    is the plen-dispatch reference path.  Shared by both engines so the
    prefill semantics can never drift between the oracle and the
    continuous scheduler.
    """
    plen = prompts.shape[1]
    logits = None
    calls = 0
    if chunk and plen > 1:
        for s0 in range(0, plen, chunk):
            seg = jnp.asarray(prompts[:, s0:s0 + chunk])
            logits, cache = decode_chunk_fn(params, cache, seg)
            calls += 1
    else:
        for t in range(plen):               # lockstep exact prefill
            logits, cache = decode_fn(params, cache,
                                      jnp.asarray(prompts[:, t:t + 1]))
            calls += 1
    return logits, cache, calls


def _serving_programs(cfg: ArchConfig):
    """The jitted decode step and prefill chunk, named so that each device
    op's module in a trace says which of the two it belongs to.

    Both donate the cache they are handed (argument 1): the layer scan
    writes each token into the stacked cache in place, and donation lets
    that update alias the caller's buffer instead of a fresh copy.  The
    input cache is deleted by the call, so a caller passes only a cache it
    owns and replaces it with the returned one."""
    def serve_decode_step(params, cache, tok):
        return decode_step(cfg, params, cache, tok)

    def serve_prefill_chunk(params, cache, toks):
        return decode_chunk(cfg, params, cache, toks)

    return (jax.jit(serve_decode_step, donate_argnums=(1,)),
            jax.jit(serve_prefill_chunk, donate_argnums=(1,)))


@jax.jit
def serve_greedy(logits):
    """The greedy token of each row of ``logits`` (..., vocab), as int32 on
    the device: the continuous engine's one pick, so that only the ids
    leave the chip and the logits never do.  Ties go to the first index
    among equal maxima, numpy's rule, on the same values the host argmax
    of the static engine reads."""
    with jax.named_scope("lm_head"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class _EngineBase:
    """Shared observability + resilience plumbing (DESIGN.md §11/§12).

    All obs hooks are no-ops behind one ``obs.enabled()`` check, so the
    disabled (default) path costs a boolean test — never a dict walk.

    Resilience state is initialized by ``_init_resilience`` (called by
    both constructors, with None when disabled); every resilience branch
    in the hot path is one ``is None`` test.
    """

    _obs_engine = "?"

    def prefill_logits(self, prompts: np.ndarray):
        """Last-token logits of equal-length ``prompts`` (B, S) through
        this engine's own prefill dispatches on a fresh cache — the
        served logits to hold against a full-sequence forward."""
        cache = init_cache(self.cfg, prompts.shape[0], self.max_len,
                           self.cache_dtype)
        logits, _, _ = _run_prefill(self._decode, self._decode_chunk,
                                    self.params, cache,
                                    np.asarray(prompts, np.int32),
                                    self.prefill_chunk)
        return logits

    # -- resilience (DESIGN.md §12) ----------------------------------------

    def _init_resilience(self, resilience: Optional[ResilienceConfig]):
        """Wire the optional resilience layer; must run after ``self.params``
        is set and BEFORE the weight accounting (a degradation ladder's
        rung 0 replaces the constructor's params)."""
        self.resilience = resilience
        self.dropped: List[Request] = []    # shed + deadline-expired
        self.slow_steps = 0                 # detector flags (host counter)
        self._clock_skew_s = 0.0            # chaos clock-skew lands here
        self._tick = 0                      # step/round index (1-based)
        self._guard: Optional[PayloadGuard] = None
        self._detector = None
        self._rung = 0
        self._streak_over = 0
        self._streak_under = 0
        self._degrade_cooldown = 0
        self.rung_history: List[tuple] = []  # [(tick, rung name, direction)]
        # hot-swap state (DESIGN.md §15): staged tree applied at the next
        # step boundary + optional requant actuator bound after construction
        self._pending_swap: Optional[tuple] = None     # (tree, reason)
        self.swap_history: List[tuple] = []            # [(tick, reason)]
        self.requant = None
        if resilience is None:
            return
        self._detector = resilience.make_detector()
        if resilience.degrade is not None:
            # the engine serves rung 0 of the ladder from the start
            name, tree = resilience.degrade.ladder[0]
            self.params = tree
            self.rung_history.append((0, name, "init"))
        if resilience.integrity_every:
            self._guard = PayloadGuard(self.params)

    def _now(self) -> float:
        """Wall-clock stamp source for stats/latency accounting.

        perf_counter plus the chaos clock-skew offset — skew-vulnerable BY
        DESIGN so the clock-skew fault visibly lands in the stats clock,
        proving deadlines (which ride ``time.monotonic`` directly) never
        consult it.
        """
        return time.perf_counter() + self._clock_skew_s

    def _submit_common(self, req: "Request") -> bool:
        """Arrival stamping + deadline default + load shedding.

        Returns False (and records the drop) when the bounded queue is
        full; the caller must not enqueue in that case.
        """
        if req.arrival_s is None:
            req.arrival_s = self._now()
        if req.arrival_mono is None:
            req.arrival_mono = time.monotonic()
        res = self.resilience
        if res is not None:
            if req.deadline_s is None:
                req.deadline_s = res.default_deadline_s
            if res.queue_cap is not None and len(self.queue) >= res.queue_cap:
                self._drop(req, "shed-queue-full")
                return False
        return True

    def _drop(self, req: "Request", reason: str, slot=None) -> None:
        """Record a shed/expired request — reported, never silent."""
        req.dropped = True
        req.drop_reason = reason
        self.dropped.append(req)
        if obs.enabled():
            kw = {} if slot is None else {"slot": int(slot)}
            obs.instant("serve.request.dropped", rid=req.rid, reason=reason,
                        engine=self._obs_engine, **kw)
            obs.counter("repro_serve_dropped_total", reason=reason,
                        engine=self._obs_engine).inc()

    def _deadline_expired(self, req: "Request", now_mono: float) -> bool:
        return (req.deadline_s is not None
                and req.arrival_mono is not None
                and now_mono - req.arrival_mono > req.deadline_s)

    def _expire_queue(self) -> None:
        """Drop queued requests whose deadline passed (before admission —
        prefilling a request that can no longer finish in time is the
        worst way to spend a dispatch)."""
        if self.resilience is None or not self.queue:
            return
        now_mono = time.monotonic()
        keep: deque = deque()
        for r in self.queue:
            if self._deadline_expired(r, now_mono):
                self._drop(r, "deadline")
            else:
                keep.append(r)
        self.queue = keep

    def _retry(self, site: str, fn):
        """Run ``fn`` under the transient-retry policy (fail fast if none).

        Only the configured transient types (chaos.InjectedFault plus
        ``ResilienceConfig.transient``) are retried; anything else — and
        transient faults past the restart budget — propagates.
        """
        res = self.resilience
        if res is None or res.retry is None:
            return fn()
        policy = res.retry
        transient = res.transient_types()
        failures = 0
        while True:
            try:
                out = fn()
            except transient as e:
                delay = policy.next_delay()
                if delay is None:
                    raise
                failures += 1
                if obs.enabled():
                    obs.instant("resilience.retry", site=site,
                                engine=self._obs_engine, delay_s=delay,
                                error=type(e).__name__)
                    obs.counter("repro_serve_retries_total", site=site,
                                engine=self._obs_engine).inc()
                res.retry_sleep(delay)
            else:
                policy.record_success()
                if failures and obs.enabled():
                    obs.counter("repro_serve_recovered_total", site=site,
                                engine=self._obs_engine).inc()
                return out

    def _verify_integrity(self) -> None:
        """Checksum the serving payloads; heal exact bytes on mismatch."""
        res = self.resilience
        if self._guard is None or self._tick % res.integrity_every != 0:
            return
        corrupted = self._guard.verify(self.params)
        if not corrupted:
            return
        with obs.span("resilience.heal", engine=self._obs_engine,
                      paths=list(corrupted)):
            self.params = self._guard.heal(self.params, corrupted)
        if obs.enabled():
            obs.counter("repro_serve_integrity_corrupt_total",
                        engine=self._obs_engine).inc(len(corrupted))
            obs.counter("repro_serve_integrity_healed_total",
                        engine=self._obs_engine).inc(len(corrupted))

    def _maybe_degrade(self) -> None:
        """Watermark ladder walk: sustained overload → one rung down,
        sustained calm → one rung up (never past either end)."""
        res = self.resilience
        pol = res.degrade if res is not None else None
        if pol is None:
            return
        depth = len(self.queue)
        if depth >= pol.high_watermark:
            self._streak_over += 1
            self._streak_under = 0
        elif depth <= pol.low_watermark:
            self._streak_under += 1
            self._streak_over = 0
        else:
            self._streak_over = self._streak_under = 0
        if self._degrade_cooldown > 0:
            self._degrade_cooldown -= 1
            return
        if self._streak_over >= pol.streak and self._rung < len(pol.ladder) - 1:
            self._set_rung(self._rung + 1, "down", depth)
        elif self._streak_under >= pol.streak and self._rung > 0:
            self._set_rung(self._rung - 1, "up", depth)

    def _set_rung(self, rung: int, direction: str, depth: int) -> None:
        """Hot-swap the param tree to ladder rung ``rung`` (step boundary:
        the KV cache is weight-format-independent, in-flight slots keep
        decoding)."""
        pol = self.resilience.degrade
        name, tree = pol.ladder[rung]
        self._rung = rung
        self._swap_tree(tree, reason=f"degrade:{name}")
        self._degrade_cooldown = pol.cooldown_steps
        self._streak_over = self._streak_under = 0
        self.rung_history.append((self._tick, name, direction))
        if obs.enabled():
            obs.instant("resilience.degrade", engine=self._obs_engine,
                        rung=name, direction=direction, queue_depth=depth)
            obs.counter("repro_serve_degrade_total", engine=self._obs_engine,
                        direction=direction).inc()

    # -- generic hot-swap (DESIGN.md §15) -----------------------------------

    def request_swap(self, tree, *, reason: str = "requant") -> None:
        """Stage a new served tree, applied at the NEXT step boundary —
        never mid-step: the in-flight dispatch finishes on the old tree,
        and the KV cache is weight-format-independent, so slots drain
        and refill across the swap with no serving gap.  A second
        request before the boundary replaces the first (last writer
        wins — both trees are whole-model artifacts)."""
        self._pending_swap = (tree, reason)

    def _apply_pending_swap(self) -> None:
        if self._pending_swap is None:
            return
        tree, reason = self._pending_swap
        self._pending_swap = None
        self._swap_tree(tree, reason=reason)

    def _swap_tree(self, tree, *, reason: str) -> None:
        """Swap the served param tree — the generalized form of the
        degrade-ladder rung swap, shared by degradation and requant.

        Refreshes byte/format accounting, REBASELINES the integrity
        guard on the new pristine payloads (a guard keyed to the old
        tree would flag a legitimate swap as corruption and "heal" back
        to stale bytes), and notifies the quality monitor so cached
        expected-distortion entries for the old codes drop.
        """
        self.params = tree
        self.weight_bytes, self.weight_bytes_bf16 = qweight_bytes(tree)
        self.weight_formats = leaf_format_histogram(tree)
        if self._guard is not None:
            self._guard = PayloadGuard(tree)
        self.swap_history.append((self._tick, reason))
        if self._quality is not None:
            hook = getattr(self._quality, "on_swap", None)
            if hook is not None:
                hook(reason=reason)
        if obs.enabled():
            obs.instant("serve.swap", engine=self._obs_engine, reason=reason,
                        tick=self._tick)
            obs.counter("repro_serve_swaps_total",
                        engine=self._obs_engine).inc()

    def attach_requant(self, actuator) -> None:
        """Bind a ``serve.requant`` actuator; the engine polls it once
        per step after quality sampling, behind the same obs gate."""
        self.requant = actuator

    def _poll_requant(self) -> None:
        if self.requant is not None and self._quality is not None \
                and obs.enabled():
            self.requant.poll(self)

    def _observe_step_time(self, dt: float) -> None:
        if self._detector is not None and self._detector.observe(dt):
            self.slow_steps += 1
            if obs.enabled():
                obs.instant("resilience.slow_step", engine=self._obs_engine,
                            step_s=dt)
                obs.counter("repro_serve_slow_steps_total",
                            engine=self._obs_engine).inc()

    # -- observability (DESIGN.md §11) --------------------------------------

    def _obs_arrival(self, req: "Request") -> None:
        if obs.enabled():
            obs.instant("serve.request.arrival", rid=req.rid,
                        engine=self._obs_engine)
            obs.gauge("repro_serve_queue_depth",
                      engine=self._obs_engine).set(len(self.queue))

    def _obs_request_done(self, req: "Request", slot=None) -> None:
        kw = {} if slot is None else {"slot": int(slot)}
        obs.instant("serve.request.finish", rid=req.rid,
                    engine=self._obs_engine, **kw)
        obs.counter("repro_serve_finished_total",
                    engine=self._obs_engine).inc()
        if req.ttft_s is not None:
            obs.histogram("repro_serve_ttft_seconds",
                          engine=self._obs_engine).observe(req.ttft_s)
        if req.tpot_s is not None:
            obs.histogram("repro_serve_tpot_seconds",
                          engine=self._obs_engine).observe(req.tpot_s)


class ServeEngine(_EngineBase):
    """Static-batching rounds — the reference scheduler (DESIGN.md §6)."""

    _obs_engine = "static"

    def __init__(self, cfg: ArchConfig, params, *,
                 config: Optional[EngineConfig] = None, **kwargs):
        config = resolve_engine_config(config, kwargs, where="ServeEngine")
        self.config = config
        self.cfg = cfg
        self.params = params
        self.n_slots = config.n_slots
        self.max_len = config.max_len
        self.cache_dtype = config.cache_dtype
        self.prefill_chunk = config.prefill_chunk
        self._quality = config.quality   # optional serve.quality monitor
        self.queue: deque[Request] = deque()
        self.round_stats: List[RoundStats] = []
        self._init_resilience(config.resilience)  # may swap params to rung 0
        # mixed-rate serving visibility (DESIGN.md §10): realized weight
        # HBM bytes vs bf16 and the per-leaf format mix of this engine
        self.weight_bytes, self.weight_bytes_bf16 = qweight_bytes(self.params)
        self.weight_formats = leaf_format_histogram(self.params)
        step_fn, chunk_fn = _serving_programs(cfg)
        self._decode = config.decode_fn or step_fn
        self._decode_chunk = config.decode_chunk_fn or chunk_fn

    def submit(self, req: Request) -> bool:
        if not self._submit_common(req):
            return False
        self.queue.append(req)
        self._obs_arrival(req)
        return True

    def _admit(self) -> List[Request]:
        """Pop up to n_slots queued requests sharing the head's prompt len."""
        if not self.queue:
            return []
        plen = len(self.queue[0].prompt)
        admitted, rest = [], deque()
        while self.queue and len(admitted) < self.n_slots:
            r = self.queue.popleft()
            if len(r.prompt) == plen:
                admitted.append(r)
            else:
                rest.append(r)
        rest.extend(self.queue)
        self.queue = rest
        return admitted

    def _prefill(self, cache, prompts: np.ndarray):
        return _run_prefill(self._decode, self._decode_chunk, self.params,
                            cache, prompts, self.prefill_chunk)

    def run_round(self) -> List[Request]:
        """One static-batching round; returns the finished requests."""
        self._tick += 1
        self._apply_pending_swap()      # round boundary: staged tree lands
        if chaos.enabled():
            # the one static-engine hook site; raising faults are retried
            # (nothing has been admitted yet, so a retry is trivially safe)
            self._retry("serve.round",
                        lambda: chaos.fire("serve.round", engine=self))
        if self.resilience is not None:
            self._verify_integrity()
            self._expire_queue()
            self._maybe_degrade()
        batch = self._admit()
        if not batch:
            return []
        b = len(batch)
        plen = len(batch[0].prompt)
        budget = max(r.max_new_tokens for r in batch)
        assert plen + budget <= self.max_len, "round exceeds cache length"
        cache = init_cache(self.cfg, b, self.max_len, self.cache_dtype)

        prompts = np.stack([r.prompt for r in batch]).astype(np.int32)
        with obs.span("serve.prefill", engine="static", batch=b) as sp:
            t0 = self._now()
            logits, cache, prefill_calls = self._prefill(cache, prompts)
            jax.block_until_ready(logits)
            t1 = self._now()       # BEFORE the host argmax transfer: the
            # transfer + argmax consume the first generated token, so they
            # are decode-side work, not prompt work.
            sp.stamp(t0, t1)
            sp.set(calls=prefill_calls)
        with obs.span("serve.decode", engine="static", batch=b) as sp:
            last = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
            # Budget-exact generation: consume `last` first, decode only
            # while some request still has budget left.  Each slot stops at
            # exactly its own max_new_tokens (mixed budgets share the batch;
            # finished slots keep stepping their cache but emit nothing),
            # and the number of decode calls is exactly max(budgets) - 1 —
            # no trailing decode whose logits nobody consumes.
            decode_steps = 0
            while True:
                t_tok = self._now()
                for i, r in enumerate(batch):
                    if len(r.out_tokens) < r.max_new_tokens:
                        r.out_tokens.append(int(last[i]))
                        if r.first_token_s is None:
                            r.first_token_s = t_tok
                        if len(r.out_tokens) >= r.max_new_tokens:
                            r.finish_s = t_tok
                if all(len(r.out_tokens) >= r.max_new_tokens
                       for r in batch):
                    break
                assert decode_steps < budget, \
                    "decode loop exceeded round budget"
                decode_steps += 1
                logits, cache = self._decode(self.params, cache,
                                             jnp.asarray(last[:, None]))
                last = np.argmax(np.asarray(logits),
                                 axis=-1).astype(np.int32)
            t2 = self._now()
            sp.stamp(t1, t2)
            sp.set(calls=decode_steps)
        st = RoundStats(
            batch=b, prompt_len=plen, prefill_calls=prefill_calls,
            prefill_s=t1 - t0, decode_calls=decode_steps, decode_s=t2 - t1,
            new_tokens=sum(len(r.out_tokens) for r in batch),
            ttft_s=[r.ttft_s for r in batch],
            tpot_s=[r.tpot_s for r in batch if r.tpot_s is not None])
        self.round_stats.append(st)
        if obs.enabled():
            obs.counter("repro_serve_rounds_total").inc()
            obs.counter("repro_serve_admitted_total",
                        engine="static").inc(b)
            obs.counter("repro_serve_tokens_total",
                        engine="static").inc(st.new_tokens)
            obs.gauge("repro_serve_queue_depth",
                      engine="static").set(len(self.queue))
            for r in batch:
                self._obs_request_done(r)
        for r in batch:
            r.done = True
        self._observe_step_time(t2 - t0)
        if self._quality is not None and obs.enabled():
            # quality observatory sampling (DESIGN.md §14) — reached only
            # with obs on AND a monitor attached, so the default serving
            # path stays byte-identical
            self._quality.observe_step(self, t2 - t0, batch)
        self._poll_requant()
        return batch

    def run_until_done(self, max_rounds: int = 1000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_rounds):
            if not self.queue:
                break
            done.extend(self.run_round())
        return done


class ContinuousEngine(_EngineBase):
    """Continuous-batching scheduler: per-slot decode streams with
    in-flight admission and eviction (DESIGN.md §9).

    One persistent cache of ``n_slots`` rows with a per-slot position
    vector.  Every :meth:`step` (i) admits queued requests into free slots
    — the whole admission burst co-prefills its common prefix in one
    lockstep chunked ``decode_chunk`` stream, finishes ragged tails
    per-row, and grafts each row into its slot — then (ii) issues ONE
    lockstep ``decode_step`` over all slots (idle slots feed a pad token;
    their rows are isolated garbage), picks each slot's greedy token on the
    device (``serve_greedy``), copies only the (B,) int32 ids to the host
    and appends each active slot's, and (iii) evicts slots whose budget
    filled, freeing them for the next step's admissions.

    Token streams are exactly those of the static reference: prefill is
    decode_chunk (bit-exact vs per-token), attention/MLP decode is
    row-wise so the mixed batch never couples slots (MoE capacity buffers
    DO couple rows across a batch — continuous-vs-static token exactness
    is a dense/ssm/hybrid property; see DESIGN.md §9).
    """

    _obs_engine = "continuous"

    def __init__(self, cfg: ArchConfig, params, *,
                 config: Optional[EngineConfig] = None, **kwargs):
        config = resolve_engine_config(config, kwargs,
                                       where="ContinuousEngine")
        self.config = config
        self.cfg = cfg
        self.params = params
        self.n_slots = config.n_slots
        self.max_len = config.max_len
        self.cache_dtype = config.cache_dtype
        self.prefill_chunk = config.prefill_chunk
        self._quality = config.quality   # optional serve.quality monitor
        self.reset_on_evict = config.reset_on_evict
        self.queue: deque[Request] = deque()
        self.step_stats: List[StepStats] = []
        self.finished: List[Request] = []
        self._init_resilience(config.resilience)  # may swap params to rung 0
        self.weight_bytes, self.weight_bytes_bf16 = qweight_bytes(self.params)
        self.weight_formats = leaf_format_histogram(self.params)
        step_fn, chunk_fn = _serving_programs(cfg)
        self._decode = config.decode_fn or step_fn
        self._decode_chunk = config.decode_chunk_fn or chunk_fn
        # the engine is the sole owner of the slot cache, so graft/reset can
        # donate it — in-place row updates instead of a full cache copy
        self._write_slot = jax.jit(cache_write_slot, donate_argnums=(0,))
        self._reset_slot = jax.jit(cache_reset_slot, donate_argnums=(0,))
        max_len, cache_dtype = self.max_len, self.cache_dtype

        # an admission burst's sub-cache and its per-row slices, named
        # programs so that their device time is attributable in a trace
        def serve_init_cache(g):
            return init_cache(cfg, g, max_len, cache_dtype)

        def serve_admit_row(sub, logits, i):
            with jax.named_scope("kv_cache"):
                kv_i, ex_i = jax.tree.map(
                    lambda t: jax.lax.dynamic_slice_in_dim(t, i, 1, axis=1),
                    (sub.kv, sub.extras))
            return (sub._replace(kv=kv_i, extras=ex_i),
                    jax.lax.dynamic_slice_in_dim(logits, i, 1))

        self._init_sub = jax.jit(serve_init_cache, static_argnums=0)
        self._admit_row = jax.jit(serve_admit_row)
        self.cache = init_cache(cfg, self.n_slots, self.max_len,
                                self.cache_dtype, per_slot=True)
        self.slots: List[Optional[Request]] = [None] * self.n_slots
        self._last = np.zeros((self.n_slots,), np.int32)  # next input token
        # aggregate dispatch/wall accounting (serve_bench reads these)
        self.prefill_calls = 0
        self.prefill_s = 0.0
        self.decode_calls = 0
        self.decode_s = 0.0

    # -- scheduler ----------------------------------------------------------

    def submit(self, req: Request) -> bool:
        assert len(req.prompt) + req.max_new_tokens <= self.max_len, \
            f"request {req.rid} exceeds cache length"
        if not self._submit_common(req):
            return False
        self.queue.append(req)
        self._obs_arrival(req)
        return True

    @property
    def active_slots(self) -> int:
        return sum(1 for r in self.slots if r is not None)

    def _admit_many(self, pairs, finished: List[Request]) -> None:
        """Prefill a burst of admissions together, then graft each slot.

        All requests admitted in the same scheduler step share a lockstep
        chunked prefill over their COMMON prefix length (one batch-G
        dispatch per chunk — the same amortization a static round gets),
        and each longer prompt finishes its ragged tail on its own batch-1
        row.  decode_chunk is row-independent and bit-exact vs per-token,
        so the grouped prefill changes no request's stream (fuzzed in
        tests/test_continuous_batching.py).

        ``prefill_s`` bills the burst from before its sub-cache is
        allocated (``serve_init_cache``) to logits-ready, and each tail
        from its first dispatch to logits-ready: the allocation counts as
        prefill, the row slices, grafts and first-token picks do not (each
        pick runs on the device, ``serve_greedy``, and only its id leaves
        the chip).  In a device trace the allocation shows apart, as the
        ``kv_cache`` op of the ``serve_init_cache`` module inside
        ``serve.admit.prefill``.
        """
        g = len(pairs)
        reqs = [r for _, r in pairs]
        slots = [s for s, _ in pairs]
        common = min(len(r.prompt) for r in reqs)
        with obs.span("serve.admit", engine="continuous", g=g, slots=slots,
                      common_len=common) as admit_span:
            with obs.span("serve.admit.prefill", alias="serve.prefill",
                          engine="continuous", slots=slots,
                          common_len=common) as sp:
                t0 = self._now()
                for r in reqs:
                    r.admitted_s = t0
                sub = self._init_sub(g)
                toks = np.stack([np.asarray(r.prompt[:common], np.int32)
                                 for r in reqs])
                logits, sub, calls = _run_prefill(
                    self._decode, self._decode_chunk, self.params, sub, toks,
                    self.prefill_chunk)
                jax.block_until_ready(logits)
                t1 = self._now()
                sp.stamp(t0, t1)
                sp.set(calls=calls)
            self.prefill_s += t1 - t0
            for i, (slot, req) in enumerate(pairs):
                if g == 1:
                    sub_i, log_i = sub, logits
                else:
                    with obs.span("serve.admit.graft", tid=slot, slot=slot,
                                  rid=req.rid):
                        sub_i, log_i = self._admit_row(sub, logits,
                                                       np.int32(i))
                tail = np.asarray(req.prompt[common:], np.int32)
                if tail.size:
                    with obs.span("serve.admit.tail", alias="serve.prefill",
                                  tid=slot, engine="continuous", slot=slot,
                                  rid=req.rid) as sp:
                        t_tail = self._now()
                        log_i, sub_i, c_tail = _run_prefill(
                            self._decode, self._decode_chunk, self.params,
                            sub_i, tail[None, :], self.prefill_chunk)
                        jax.block_until_ready(log_i)
                        t_tail_end = self._now()
                        sp.stamp(t_tail, t_tail_end)
                        sp.set(calls=c_tail)
                    self.prefill_s += t_tail_end - t_tail
                    calls += c_tail
                with obs.span("serve.admit.first_token", tid=slot,
                              slot=slot, rid=req.rid) as sp:
                    ids = serve_greedy(log_i)
                    first = int(np.asarray(ids)[0])
                    sp.set(bytes=ids.nbytes)
                if obs.enabled():
                    obs.counter("repro_serve_host_bytes_total",
                                engine="continuous",
                                site="first_token").inc(ids.nbytes)
                with obs.span("serve.admit.graft", tid=slot, slot=slot,
                              rid=req.rid):
                    self.cache = self._write_slot(
                        self.cache, sub_i, jnp.asarray(slot, jnp.int32))
                t_tok = self._now()
                req.first_token_s = t_tok
                req.out_tokens.append(first)
                self.slots[slot] = req
                self._last[slot] = first
                if obs.enabled():
                    obs.instant("serve.request.first_token", rid=req.rid,
                                slot=slot, engine="continuous")
                if len(req.out_tokens) >= req.max_new_tokens:
                    self._finish(slot, req, t_tok, finished)
            admit_span.stamp(t0, t_tok)
        self.prefill_calls += calls
        if obs.enabled():
            obs.counter("repro_serve_admitted_total",
                        engine="continuous").inc(g)
            obs.counter("repro_serve_tokens_total",
                        engine="continuous").inc(g)

    def _finish(self, slot: int, req: Request, t: float,
                finished: List[Request]) -> None:
        req.done = True
        req.finish_s = t
        self.slots[slot] = None
        self._last[slot] = 0
        if self.reset_on_evict:
            # hygiene mode: zero the freed row now.  Functionally optional —
            # the admission graft fully overwrites a slot's state rows and
            # position, and an idle slot's garbage decode is row-isolated —
            # but it costs one dispatch per eviction, so the default leaves
            # the stale row in place until refill.
            self.cache = self._reset_slot(self.cache,
                                          jnp.asarray(slot, jnp.int32))
        self.finished.append(req)
        finished.append(req)
        if obs.enabled():
            obs.counter("repro_serve_evicted_total").inc()
            self._obs_request_done(req, slot=slot)

    def _expire_slots(self) -> None:
        """Cancel in-flight requests whose deadline passed; free the slot.

        The freed row's stale cache state is handled exactly like an
        eviction's (overwritten by the next graft; optionally zeroed now
        under ``reset_on_evict``).
        """
        now_mono = time.monotonic()
        for i, r in enumerate(self.slots):
            if r is not None and self._deadline_expired(r, now_mono):
                self.slots[i] = None
                self._last[i] = 0
                if self.reset_on_evict:
                    self.cache = self._reset_slot(self.cache,
                                                  jnp.asarray(i, jnp.int32))
                self._drop(r, "deadline", slot=i)

    def _admit_burst(self, pairs, finished: List[Request]) -> None:
        """Chaos-hooked admission entry: the admission-failure fault fires
        here, BEFORE any prefill/graft state mutation, so a retry replays
        the identical burst."""
        if chaos.enabled():
            chaos.fire("serve.admit", engine=self)
        self._admit_many(pairs, finished)

    def _decode_dispatch(self):
        """Chaos-hooked decode entry (device-loss / slow-step site).

        Reads params/cache/_last and returns (logits, new_cache); the
        caller commits the cache only on success.  The step donates the
        cache, so the call itself consumes ``self.cache``: a retry
        recomputes from identical inputs only because every fault fires
        here, BEFORE the jitted call.  Keep any new fault site ahead of
        it.
        """
        if chaos.enabled():
            chaos.fire("serve.decode", engine=self)
        return self._decode(self.params, self.cache,
                            jnp.asarray(self._last[:, None]))

    def step(self) -> List[Request]:
        """One scheduler iteration: admit → lockstep decode → evict.

        Returns the requests that finished during this step.  With
        resilience configured the step additionally: fires the serve.step
        chaos hook, heals corrupted payloads, expires deadlined requests
        (queued and in-flight), walks the degradation ladder, retries
        transient admission/decode faults, and snapshots periodically.
        """
        finished: List[Request] = []
        with obs.span("serve.step", engine="continuous") as step_span:
            self._tick += 1
            self._apply_pending_swap()  # step boundary: staged tree lands
            t0 = self._now()
            if chaos.enabled():
                chaos.fire("serve.step", engine=self)
            if self.resilience is not None:
                self._verify_integrity()
                self._expire_queue()
                self._expire_slots()
                self._maybe_degrade()
            pairs = []
            while self.queue and None in self.slots:
                slot = self.slots.index(None)
                req = self.queue.popleft()
                self.slots[slot] = req      # reserve before the next index()
                pairs.append((slot, req))
            admitted = len(pairs)
            if pairs:
                try:
                    self._retry("serve.admit",
                                lambda: self._admit_burst(pairs, finished))
                except BaseException:
                    # retry budget exhausted (or non-transient): un-reserve
                    # the untouched requests and put them back at the FRONT
                    # of the queue in arrival order, so nothing is silently
                    # lost.  (injection fires before _admit_many mutates
                    # anything, so an injected-fault unwind always finds
                    # them untouched)
                    for slot, req in pairs:
                        if self.slots[slot] is req and not req.out_tokens:
                            self.slots[slot] = None
                    for slot, req in reversed(pairs):
                        if not req.out_tokens and not req.dropped:
                            self.queue.appendleft(req)
                    raise
            active = [i for i, r in enumerate(self.slots) if r is not None]
            decoded = self._decode_round(active, finished) if active else 0
            t_end = self._now()
            step_span.stamp(t0, t_end)
            step_span.set(active=len(active), admitted=admitted,
                          finished=len(finished))
            self.step_stats.append(StepStats(
                active=len(active), admitted=admitted,
                finished=len(finished), new_tokens=admitted + decoded,
                step_s=t_end - t0))
            if obs.enabled():
                obs.counter("repro_serve_tokens_total",
                            engine="continuous").inc(decoded)
                obs.gauge("repro_serve_slots_active",
                          engine="continuous").set(self.active_slots)
                obs.gauge("repro_serve_queue_depth",
                          engine="continuous").set(len(self.queue))
            self._observe_step_time(t_end - t0)
            if self._quality is not None and obs.enabled():
                # quality observatory sampling (DESIGN.md §14) — reached
                # only with obs on AND a monitor attached, so the default
                # serving path stays byte-identical
                self._quality.observe_step(self, t_end - t0, self.slots)
            self._poll_requant()
            res = self.resilience
            if (res is not None and res.snapshot_every and res.snapshot_dir
                    and self._tick % res.snapshot_every == 0):
                self.snapshot(res.snapshot_dir)
        return finished

    def _decode_round(self, active: List[int],
                      finished: List[Request]) -> int:
        """One lockstep decode over every slot: dispatch the step and the
        greedy pick on its logits (``serve_greedy``), wait, copy the (B,)
        int32 ids to the host, then the token appends and evictions.  The
        logits never leave the chip.  ``decode_s`` bills dispatch to ids
        on the host.  Returns the tokens emitted."""
        with obs.span("serve.decode", engine="continuous",
                      slots=active) as sp:
            td = self._now()
            with obs.span("serve.decode.dispatch"):
                logits, new_cache = self._retry("serve.decode",
                                                self._decode_dispatch)
                ids = serve_greedy(logits)
                # the ids leave for the host as soon as the pick ends, not
                # after the wait below has woken this thread
                ids.copy_to_host_async()
            self.cache = new_cache
            with obs.span("serve.decode.wait"):
                jax.block_until_ready(ids)
            with obs.span("serve.decode.sync", bytes=ids.nbytes):
                last = np.asarray(ids)
            t_tok = self._now()
            if obs.enabled():
                obs.counter("repro_serve_host_bytes_total",
                            engine="continuous",
                            site="decode").inc(ids.nbytes)
            sp.stamp(td, t_tok)
            self.decode_calls += 1
            self.decode_s += t_tok - td
            with obs.span("serve.decode.commit"):
                for i in active:
                    r = self.slots[i]
                    r.out_tokens.append(int(last[i]))
                    self._last[i] = last[i]
                    if len(r.out_tokens) >= r.max_new_tokens:
                        self._finish(i, r, t_tok, finished)
        return len(active)

    # -- snapshot / resume (DESIGN.md §12) ----------------------------------

    @staticmethod
    def _req_record(r: Request) -> dict:
        """JSON-portable request record for the snapshot manifest."""
        return {"rid": r.rid,
                "prompt": np.asarray(r.prompt).tolist(),
                "max_new_tokens": r.max_new_tokens,
                "out_tokens": list(r.out_tokens),
                "deadline_s": r.deadline_s,
                "arrival_s": r.arrival_s,
                "first_token_s": r.first_token_s}

    def snapshot(self, ckpt_dir: str, *, keep: Optional[int] = None) -> str:
        """Write a crash-consistent engine snapshot via ``dist.checkpoint``.

        Device state (slot cache + next-token vector) goes in the
        checkpoint payload; host scheduler state (slot/queue request
        records, tick, rung) rides the manifest's ``extra_meta`` JSON.
        The write is atomic (rename-committed step dir), so a kill at any
        moment leaves the last committed snapshot restorable —
        :meth:`resume` rebuilds an engine whose subsequent token streams
        are bit-identical to the uninterrupted run's.
        """
        from repro.dist.checkpoint import save_checkpoint
        res = self.resilience
        if keep is None:
            keep = res.snapshot_keep if res is not None else 3
        state = {"cache": self.cache, "last": jnp.asarray(self._last)}
        meta = {
            "engine": {"n_slots": self.n_slots, "max_len": self.max_len,
                       "prefill_chunk": self.prefill_chunk,
                       "reset_on_evict": self.reset_on_evict,
                       "tick": self._tick, "rung": self._rung},
            "slots": [None if r is None else self._req_record(r)
                      for r in self.slots],
            "queue": [self._req_record(r) for r in self.queue],
        }
        with obs.span("resilience.snapshot", engine="continuous",
                      step=self._tick) as sp:
            path = save_checkpoint(ckpt_dir, self._tick, state, keep=keep,
                                   extra_meta=meta)
            sp.set(path=str(path))
        if obs.enabled():
            obs.counter("repro_serve_snapshots_total",
                        engine="continuous").inc()
        return str(path)

    @classmethod
    def resume(cls, ckpt_dir: str, cfg: ArchConfig, params, *,
               step: Optional[int] = None, cache_shardings=None,
               config: Optional[EngineConfig] = None,
               **kwargs) -> "ContinuousEngine":
        """Rebuild an engine from the latest (or ``step``-th) snapshot.

        ``params`` must be the same serving tree the snapshotting engine
        held (weights are NOT stored in the snapshot — they are the
        deployment artifact, reloaded independently).  Scheduler state —
        slot assignments, partial token streams, queue order, tick — and
        the device cache come back exactly; deadline clocks restart at
        resume (``time.monotonic`` is process-local, and a revived
        request should not be instantly expired for time the engine
        spent dead).

        ``cache_shardings`` (optional) is a ``{"cache": ..., "last": ...}``
        pytree of shardings for the restored state — the sharded-serving
        path passes its mesh layout here so the cache lands directly on
        the mesh.  Without it the cache restores UNCOMMITTED (a fresh
        ``init_cache``-like placement): ``dist.checkpoint._place`` ignores
        the accidental single-device commitment of a plain template leaf.
        """
        from repro.dist.checkpoint import read_manifest, restore_checkpoint
        manifest = read_manifest(ckpt_dir, step=step)
        meta = manifest["meta"]
        em = meta["engine"]
        if config is not None:
            if kwargs:
                raise TypeError("resume: pass either config=EngineConfig"
                                "(...) or legacy kwargs, not both "
                                f"(got {sorted(kwargs)})")
        else:
            # legacy-kwarg path: snapshot geometry fills the gaps, then
            # one config is built here (resume IS the shim layer — the
            # constructor sees config= and never double-warns)
            kwargs.setdefault("n_slots", em["n_slots"])
            kwargs.setdefault("max_len", em["max_len"])
            kwargs.setdefault("prefill_chunk", em.get("prefill_chunk"))
            kwargs.setdefault("reset_on_evict",
                              em.get("reset_on_evict", False))
            config = EngineConfig(**kwargs)
        eng = cls(cfg, params, config=config)
        if eng.n_slots != em["n_slots"] or eng.max_len != em["max_len"]:
            raise ValueError(
                f"snapshot geometry (n_slots={em['n_slots']}, "
                f"max_len={em['max_len']}) does not match the engine "
                f"(n_slots={eng.n_slots}, max_len={eng.max_len})")
        template = {"cache": eng.cache, "last": np.asarray(eng._last)}
        state, _ = restore_checkpoint(ckpt_dir, template,
                                      step=manifest["step"],
                                      shardings=cache_shardings)
        eng.cache = state["cache"]
        eng._last = np.asarray(state["last"]).astype(np.int32)

        now_mono = time.monotonic()

        def revive(rec: dict) -> Request:
            req = Request(rid=rec["rid"],
                          prompt=np.asarray(rec["prompt"], np.int32),
                          max_new_tokens=rec["max_new_tokens"],
                          out_tokens=list(rec["out_tokens"]),
                          deadline_s=rec.get("deadline_s"))
            req.arrival_s = rec.get("arrival_s")
            req.first_token_s = rec.get("first_token_s")
            req.arrival_mono = now_mono
            return req

        eng.slots = [None if rec is None else revive(rec)
                     for rec in meta["slots"]]
        eng.queue = deque(revive(rec) for rec in meta["queue"])
        eng._tick = em["tick"]
        rung = em.get("rung", 0)
        if rung and eng.resilience is not None \
                and eng.resilience.degrade is not None:
            eng._set_rung(rung, "resume", len(eng.queue))
        if obs.enabled():
            obs.instant("resilience.resume", engine="continuous",
                        step=em["tick"], slots=sum(
                            1 for r in eng.slots if r is not None),
                        queued=len(eng.queue))
        return eng

    def run_until_done(self, max_steps: int = 100_000) -> List[Request]:
        """Step until idle; raise :class:`EngineStalledError` (naming the
        stuck slots and queue depth) if ``max_steps`` is exhausted with
        work still pending."""
        done: List[Request] = []
        for _ in range(max_steps):
            if not self.queue and self.active_slots == 0:
                return done
            done.extend(self.step())
        if self.queue or self.active_slots:
            stuck = [(i, r.rid, len(r.out_tokens), r.max_new_tokens)
                     for i, r in enumerate(self.slots) if r is not None]
            raise EngineStalledError(max_steps, stuck, len(self.queue))
        return done
