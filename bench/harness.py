"""One run of one cell: set up the program, warm it, drive the window.

The system under test is the program's ``ContinuousEngine``, built from
one ``EngineConfig`` as ``launch/serve.py --continuous`` builds it: bf16
KV cache, chunked prefill, no mesh on one chip.  The harness drives it
only through ``submit`` and ``step``.  It wraps the engine's admission
burst, decode dispatch and step in ``jax.profiler.TraceAnnotation`` spans
(``bench.*``) and records host times around them; nothing inside the
program is changed.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

import traffic
from spec import BENCH, ModelSpec, load_json, load_spec

ROOT = BENCH.parent
_COMPILE = "/jax/core/compile/backend_compile_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileClock:
    """Compile seconds and counts from ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.lowerings = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == _COMPILE:
            self.seconds += secs
            self.compiles += 1
        elif event == _LOWER:
            self.seconds += secs
            self.lowerings += 1


def load_cell(workload: str, root: Path = ROOT):
    """(BENCHMARK.json, the cell, its ModelSpec, its traffic mix)."""
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    mix = load_json("traffic", cell["traffic"], root / "bench")
    spec = load_spec(cell["config"], root / "bench")
    if traffic.longest(mix) > spec.max_len:
        raise SystemExit(f"{workload}: the mix sends {traffic.longest(mix)} "
                         f"tokens, the cache holds {spec.max_len}")
    return bench, cell, spec, mix


def program_config(spec: ModelSpec):
    """The program's ArchConfig for ``spec``: its registry entry with the
    file's sizes."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(spec.raw["program_arch"]), n_layers=spec.layers,
        d_model=spec.d_model, n_heads=spec.n_heads, n_kv=spec.n_kv,
        head_dim=spec.head_dim, d_ff=spec.d_ff, vocab=spec.vocab,
        activation=spec.activation, gated_mlp=spec.gated_mlp,
        rope_theta=spec.rope_theta, norm="rmsnorm", tie_embeddings=True)


def build_engine(spec: ModelSpec, params, jits=None):
    """``ContinuousEngine`` over ``params``.  ``jits`` (``engine_jits`` of an
    earlier engine of the same cell) lends its jitted programs, so that a
    process that serves several seeds traces them once; a run of
    ``run.py`` builds its own, as the engine does by default."""
    import jax.numpy as jnp
    from repro.serve import ContinuousEngine, EngineConfig
    extra = {} if jits is None else {"decode_fn": jits[0],
                                     "decode_chunk_fn": jits[1]}
    config = EngineConfig(n_slots=spec.slots, max_len=spec.max_len,
                          cache_dtype=jnp.bfloat16,
                          prefill_chunk=spec.prefill_chunk, **extra)
    return ContinuousEngine(program_config(spec), params, config=config)


def engine_jits(eng):
    """The engine's own jitted decode and chunk programs (take them before
    a ``Recorder`` wraps the engine)."""
    return eng._decode, eng._decode_chunk


@dataclasses.dataclass
class Dispatch:
    kind: str        # "chunk" (prefill, rows x tokens) | "step" (decode)
    rows: int        # real rows: burst size, or active slots
    tokens: int      # tokens per row in this dispatch


class Recorder:
    """Host-side records of the engine calls, wrapped around the engine's
    own methods on this one instance."""

    def __init__(self, eng):
        self.eng = eng
        self.dispatches: List[Dispatch] = []
        self.admit_start: Dict[int, float] = {}
        self.slot_of: Dict[int, int] = {}
        self.prefill_ctx = 0          # sum over admitted prompt positions
        self.prompt_tokens = 0
        self.decode_ctx = 0           # sum over decoded rows of context
        self._wrap()

    def _wrap(self):
        eng = self
        e = self.eng
        admit, decode, chunk, step = (e._admit_many, e._decode_dispatch,
                                      e._decode_chunk, e.step)

        def admit_many(pairs, finished):
            t = time.perf_counter()
            for slot, r in pairs:
                eng.admit_start[r.rid] = t
                eng.slot_of[r.rid] = slot
                n = len(r.prompt)
                eng.prompt_tokens += n
                eng.prefill_ctx += n * (n + 1) // 2
            with jax.profiler.TraceAnnotation("bench.admit"):
                return admit(pairs, finished)

        def decode_dispatch():
            live = [r for r in e.slots if r is not None]
            active = len(live)
            eng.decode_ctx += sum(len(r.prompt) + len(r.out_tokens)
                                  for r in live)
            eng.dispatches.append(Dispatch("step", active, 1))
            with jax.profiler.TraceAnnotation("bench.decode_dispatch"):
                return decode()

        def decode_chunk(params, cache, toks):
            eng.dispatches.append(Dispatch("chunk", toks.shape[0],
                                           toks.shape[1]))
            return chunk(params, cache, toks)

        def stepped():
            with jax.profiler.TraceAnnotation("bench.step"):
                return step()

        e._admit_many = admit_many
        e._decode_dispatch = decode_dispatch
        e._decode_chunk = decode_chunk
        e.step = stepped


def warm(eng, spec: ModelSpec) -> None:
    """Compile and run every shape the window can produce: an admission
    burst of each size 1..slots, then one decode step over full slots.
    Prompts are whole chunks, so a burst's common prefix runs at (g, chunk)
    and each row's tail at (1, chunk), the shape of the burst of one."""
    from repro.serve import Request
    c = spec.prefill_chunk
    rid = -1
    bursts = [(g, 1) for g in range(1, spec.slots + 1)] + [(spec.slots, 2)]
    for g, new in bursts:
        for _ in range(g):
            eng.submit(Request(rid=rid, prompt=np.zeros(c, np.int32),
                               max_new_tokens=new))
            rid -= 1
        eng.run_until_done()
    eng.finished.clear()
    eng.step_stats.clear()


@dataclasses.dataclass
class Window:
    """What the harness saw in one measured window."""

    seconds: float
    t0: float = 0.0
    t_end: float = 0.0                 # end of the last step begun in time
    steps: List[tuple] = dataclasses.field(default_factory=list)
    arrived: List = dataclasses.field(default_factory=list)
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0
    lowerings: int = 0
    engine: Dict[str, float] = dataclasses.field(default_factory=dict)
    first_dispatch: int = 0
    last_dispatch: int = 0
    prompt_tokens: int = 0
    prefill_ctx: int = 0
    decode_ctx: int = 0
    trace: Optional[dict] = None       # start/stop times, dispatch indices
    backlog: int = 0                   # requests queued at the close
    drain_s: float = 0.0               # stepping after the close
    held_s: float = 0.0                # the profiler held the loop
    unanswered: int = 0

    @property
    def span_s(self) -> float:
        return self.t_end - self.t0


def _counters(eng) -> Dict[str, float]:
    return {"prefill_s": eng.prefill_s, "prefill_calls": eng.prefill_calls,
            "decode_s": eng.decode_s, "decode_calls": eng.decode_calls}


def drive(eng, rec: Recorder, spec: ModelSpec, mix: dict, seed: int,
          seconds: float, clock: CompileClock,
          trace_dir: Optional[Path] = None, finish: int = 0) -> Window:
    """Serve the mix for ``seconds`` from an idle engine, then keep stepping
    (sending nothing new) until every request that arrived in the window
    has its first token and ``finish`` of them are done, at most 60 s
    more.  With ``trace_dir`` the profiler runs from the first step that
    begins after ``min(5, 0.3 * seconds)`` s to the first that begins
    ``min(10, 0.5 * seconds)`` s later, long enough to hold admissions
    at the open loop's rates; steps are never cut, so the traced window
    holds whole steps.  The seconds in which starting and stopping the
    profiler hold the loop are left out of the window: the schedule, the
    window's start and the waiting requests' arrivals move on by them."""
    from repro.serve import Request
    w = Window(seconds=seconds)
    closed = mix["loop"] == "closed"
    client_of: Dict[int, int] = {}
    if closed:
        loop = traffic.ClosedLoop(mix, seed, spec.vocab, spec.slots)
        pending = []
    else:
        pending = traffic.open_loop(mix, seed, seconds, spec.vocab)
    c0 = _counters(eng)
    comp0, low0 = clock.compiles, clock.lowerings
    w.first_dispatch = len(rec.dispatches)
    pt0, pc0, dc0 = rec.prompt_tokens, rec.prefill_ctx, rec.decode_ctx
    trace_at = min(5.0, seconds * 0.3) if trace_dir is not None else None
    trace_len = min(10.0, seconds * 0.5)

    def send(item, at, client=None):
        r = Request(rid=item.rid, prompt=item.prompt,
                    max_new_tokens=item.max_new)
        r.arrival_s = at
        client_of[r.rid] = client
        eng.submit(r)
        w.arrived.append(r)

    def hold(t_from):
        nonlocal t0
        held = time.perf_counter() - t_from
        t0 += held
        w.t0 = t0
        w.held_s += held
        for r in w.arrived:
            if r.first_token_s is None:
                r.arrival_s += held

    t0 = time.perf_counter()
    w.t0 = t0
    if closed:
        for c in range(spec.slots):
            send(loop.next(c), t0, c)
    i = 0
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        while i < len(pending) and pending[i].at_s <= now - t0:
            w.lateness_s.append(now - t0 - pending[i].at_s)
            send(pending[i], t0 + pending[i].at_s)
            i += 1
        if trace_at is not None and w.trace is None and now - t0 >= trace_at:
            held_from = time.perf_counter()
            jax.profiler.start_trace(str(trace_dir))
            hold(held_from)
            w.trace = {"start": time.perf_counter(),
                       "first": len(rec.dispatches)}
            w.trace["annotation"] = jax.profiler.TraceAnnotation(
                "bench.traced")
            w.trace["annotation"].__enter__()
        if w.trace is not None and "stop" not in w.trace \
                and now - w.trace["start"] >= trace_len:
            w.trace["annotation"].__exit__(None, None, None)
            w.trace["last"] = len(rec.dispatches)
            w.trace["stop"] = time.perf_counter()
            jax.profiler.stop_trace()
            hold(w.trace["stop"])
            continue
        if eng.queue or eng.active_slots:
            ts = time.perf_counter()
            finished = eng.step()
            te = time.perf_counter()
            st = eng.step_stats[-1]
            w.steps.append((ts, te, st.active, st.admitted, st.new_tokens))
            w.t_end = te
            if closed:
                for r in finished:
                    c = client_of[r.rid]
                    send(loop.next(c), te, c)
        elif i < len(pending):
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(min(max(pending[i].at_s - (now - t0), 0), 5e-4))
    if w.trace is not None and "stop" not in w.trace:
        w.trace["annotation"].__exit__(None, None, None)
        w.trace["last"] = len(rec.dispatches)
        w.trace["stop"] = time.perf_counter()
        jax.profiler.stop_trace()
    w.compiles = clock.compiles - comp0
    w.lowerings = clock.lowerings - low0
    c1 = _counters(eng)
    w.engine = {k: c1[k] - c0[k] for k in c0}
    w.last_dispatch = len(rec.dispatches)
    w.prompt_tokens = rec.prompt_tokens - pt0
    w.prefill_ctx = rec.prefill_ctx - pc0
    w.decode_ctx = rec.decode_ctx - dc0
    w.backlog = len(eng.queue)
    # the answers due: every request that arrived gets its first token, and
    # at least ``finish`` of them finish, so that there is a sample to check
    t_close = time.perf_counter()
    deadline = t_close + 60.0
    while (any(r.first_token_s is None for r in w.arrived)
           or sum(r.done for r in w.arrived) < finish) \
            and (eng.queue or eng.active_slots) \
            and time.perf_counter() < deadline:
        eng.step()
    w.unanswered = sum(1 for r in w.arrived if r.first_token_s is None)
    w.drain_s = time.perf_counter() - t_close
    return w


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@dataclasses.dataclass
class RunView:
    """What a metric reader (``bench/metrics/<name>.py``) is given."""

    spec: ModelSpec
    cell: dict
    mix: dict
    window: Window
    rec: Recorder
    setup_s: float
    trace: Optional[dict]       # devtrace.reduce of the traced window
    peak: dict                  # the chip's row of peaks.json

    def window_dispatches(self):
        w = self.window
        return self.rec.dispatches[w.first_dispatch:w.last_dispatch]

    def traced_dispatches(self):
        t = self.window.trace
        if not t or "last" not in t:
            return []
        return self.rec.dispatches[t["first"]:t["last"]]
