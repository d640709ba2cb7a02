"""The program's own spans and scopes, read back from a traced run.

The program opens ``serve.*`` spans on the profiler's clock (``repro.obs``),
names its jitted programs (``serve_decode_step``, ``serve_prefill_chunk``,
...) and scopes the layers of its model step (``jax.named_scope``:
``attention``, ``kv_cache``, ``lm_head``, ``mlp``, ``attn_proj``,
``packed_matmul``).  This module loads the ``.xplane.pb`` that ``run.py
--trace 1`` wrote under ``<root>/.bench_out/trace-<cell>`` and keeps:

* the host plane's ``serve.*`` spans and the ``bench.traced`` window;
* each TPU operation (line ``XLA Ops``) with its module (line ``XLA
  Modules``) and its HLO instruction.

The TPU's trace events carry no ``op_name``.  ``compiled_texts`` takes it
from the compiled HLO of the engine's decode step and of each prefill
chunk shape the traced window dispatched: lowered again in the process
that ran them, with the same arguments' shapes, they come from JAX's
in-memory cache, so the instruction names are those of the trace.
``with_paths`` puts each operation's ``op_name`` (its scope path) in
place of its instruction.

``reduce`` works on those lists alone, so it can be checked on a small
recorded trace:

* each stretch of the window in which no operation ran is charged to the
  innermost ``serve.*`` span around its midpoint, or to ``host.other``;
* each operation's device time is charged to the innermost scope in its
  path.  An operation with none is the cache's when it belongs to one of
  the engine's cache programs, or when its output has the shape of a
  cache leaf (ending in cache positions, KV heads, head size: the copies
  XLA inserts, the layer scan's slices and its writes of the stacked
  cache); any other is ``unscoped``.

A program that opens no such spans or scopes (one older than them) gives
empty tables, and the metrics that read them give nothing.
"""
from __future__ import annotations

import bisect
import re
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import devtrace

SPAN_PREFIX = "serve."
SCOPES = ("attn_proj", "attention", "kv_cache", "mlp", "lm_head",
          "packed_matmul")
UNSCOPED = "unscoped"
#: the engine's programs that do nothing but cache work
CACHE_MODULES = ("serve_init_cache", "serve_admit_row", "cache_write_slot",
                 "cache_reset_slot")
#: the programs the engine names, as the trace's module names them
ENGINE_MODULES = ("serve_decode_step", "serve_prefill_chunk") + CACHE_MODULES

Span = Tuple[str, float, float]                       # (name, start, end)
#: (label, start, end, module, instruction or scope path, output dims)
Op = Tuple[str, float, float, str, str, Tuple[int, ...]]

#: trace file → (events with scope paths, reduced), one load per run
_loaded: Dict[str, tuple] = {}


def trace_dir(root: Path, cell: str) -> Path:
    return Path(root) / ".bench_out" / f"trace-{cell}"


def output_dims(instruction: str) -> Tuple[int, ...]:
    """The dims of an instruction's (first) output, from its text:
    ``%copy.5 = bf16[40,8,640,36,64]{4,3,2,1,0} copy(...)`` gives
    ``(40, 8, 640, 36, 64)``."""
    m = re.search(r"=\s*\(?\s*[a-z0-9]+\[([0-9,]*)\]", instruction)
    if not m or not m.group(1):
        return ()
    return tuple(int(d) for d in m.group(1).split(","))


def instruction_key(text: str) -> str:
    """An instruction's name and output type, the part of its text that the
    trace event and the compiled HLO print alike: ``%fusion.80 =
    s32[8]{0:T(128)S(1)} fusion(...)`` → ``fusion.80 s32[8]{0:T(128)S(1)}``;
    a bare name (a CPU trace's ``hlo_op``) stays itself."""
    head, _, rest = text.partition(" = ")
    name = head.split()[-1].lstrip("%") if head.strip() else ""
    return f"{name} {rest.split()[0]}" if rest.strip() else name


def op_paths(texts: Sequence[str]) -> Dict[str, str]:
    """{instruction key: op_name}, and {instruction name: op_name}, of the
    compiled HLO texts."""
    out: Dict[str, str] = {}
    rx = re.compile(r'^\s*(?:ROOT\s+)?(%\S+ = \S+) .*?op_name="([^"]*)"')
    for text in texts:
        for line in text.splitlines():
            m = rx.match(line)
            if m:
                key = instruction_key(m.group(1))
                out.setdefault(key, m.group(2))
                out.setdefault(key.split()[0], m.group(2))
    return out


def with_paths(events: dict, paths: Dict[str, str]) -> dict:
    """``events`` with each operation's instruction replaced by its scope
    path (empty when the compiled texts do not name it)."""
    def path(instr):
        return paths.get(instr) or paths.get(instr.split(" ")[0], "")
    return dict(events, ops={
        plane: [op[:4] + (path(op[4]),) + op[5:] for op in ops]
        for plane, ops in events["ops"].items()})


def module_name(name: str) -> str:
    """``jit_serve_decode_step(12)`` or ``jit_serve_decode_step`` →
    ``serve_decode_step``."""
    name = re.sub(r"\(.*\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def load(path) -> dict:
    """{"window": (start, end) or None, "spans": [Span], "ops": {plane:
    [Op]}} from an .xplane.pb file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    window, spans = None, []
    ops: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == devtrace.WINDOW_SPAN and window is None:
                        window = (ev.start_ns, end)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, end))
        elif plane.name.startswith("/device:TPU:"):
            modules, evs = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(module_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events]
                elif line.name == devtrace.OP_LINE:
                    evs = list(line.events)
            modules.sort(key=lambda m: m[1])
            starts = [m[1] for m in modules]
            ops[plane.name] = [
                (devtrace.op_label(ev.name), ev.start_ns,
                 ev.start_ns + ev.duration_ns,
                 _module_at(modules, starts,
                            ev.start_ns + ev.duration_ns / 2),
                 instruction_key(ev.name), output_dims(ev.name))
                for ev in evs]
    return {"window": window, "spans": spans, "ops": ops}


def _module_at(modules: Sequence[Span], starts: Sequence[float],
               t: float) -> str:
    """The module whose run holds instant ``t`` (a device runs one module
    at a time; ``starts`` are the sorted runs' starts)."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][0] if i >= 0 and modules[i][2] >= t else ""


def scope_of(path: str, module: str, dims: Tuple[int, ...],
             cache_dims: Optional[Tuple[int, int, int]]) -> str:
    """The innermost known scope in an operation's path; without one, the
    cache's for a cache program's operation or a cache leaf's shape."""
    found = [p for p in path.split("/") if p in SCOPES]
    if found:
        return found[-1]
    if module in CACHE_MODULES or (
            cache_dims and dims[-3:] == tuple(cache_dims)):
        return "kv_cache"
    return UNSCOPED


def reduce(events: dict, cache_dims=None) -> Optional[dict]:
    """Idle seconds by innermost ``serve.*`` span, device seconds by scope
    and by module, and the count and seconds of the ``serve.*`` spans that
    lie wholly inside the traced window (each device's seconds averaged
    over the devices).  ``cache_dims`` is a cache leaf's last three dims
    (cache positions, KV heads, head size)."""
    if events["window"] is None:
        return None
    devices = {k: v for k, v in events["ops"].items() if v}
    if not devices:
        return None
    lo, hi = events["window"]
    around = [sp for sp in events["spans"] if sp[2] > lo and sp[1] < hi]
    idle: Dict[str, float] = defaultdict(float)
    scopes: Dict[str, float] = defaultdict(float)
    modules: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for ops in devices.values():
        clipped = [(max(op[1], lo), min(op[2], hi), op) for op in ops
                   if op[2] > lo and op[1] < hi]
        for s, e, (label, _, _, module, path_, dims) in clipped:
            if label in devtrace.CONTAINERS:
                continue
            sec = (e - s) * 1e-9
            scopes[scope_of(path_, module, dims, cache_dims)] += sec
            modules[module or "?"] += sec
        merged = devtrace.union([(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[_innermost(around, (a + b) / 2)] += (b - a) * 1e-9
    n = len(devices)
    count: Dict[str, int] = defaultdict(int)
    total: Dict[str, float] = defaultdict(float)
    for name, s, e in around:
        if s >= lo and e <= hi:
            count[name] += 1
            total[name] += (e - s) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n,
        "idle": {k: v / n for k, v in idle.items()},
        "scopes": {k: v / n for k, v in scopes.items()},
        "modules": {k: v / n for k, v in modules.items()},
        "span_count": dict(count),
        "span_s": dict(total),
    }


def _innermost(spans: Sequence[Span], t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "host.other"


def engine_share(reduced: dict) -> float:
    """Share of the ops' device time in the modules the engine names."""
    total = sum(reduced["modules"].values())
    named = sum(v for k, v in reduced["modules"].items()
                if k in ENGINE_MODULES)
    return named / total if total > 0 else 0.0


def report(reduced: dict, out=sys.stderr) -> None:
    """Both tables: idle by span, and device time by scope."""
    idle = sum(reduced["idle"].values())
    print(f"spans: idle {idle!r} s of a {reduced['window_s']!r} s window, "
          f"by innermost serve.* span:", file=out)
    for k, v in sorted(reduced["idle"].items(), key=lambda kv: -kv[1]):
        print(f"  {k} {v!r} s", file=out)
    busy = sum(reduced["scopes"].values())
    print(f"spans: op device time {busy!r} s by scope, "
          f"{100 * engine_share(reduced)!r} % in the engine's modules:",
          file=out)
    for k, v in sorted(reduced["scopes"].items(), key=lambda kv: -kv[1]):
        print(f"  {k} {v!r} s", file=out)


def _jitted(fn):
    """The jitted program behind ``fn``, which the harness's ``Recorder``
    may have wrapped in a plain function that holds it."""
    if hasattr(fn, "lower"):
        return fn
    return next((c.cell_contents for c in fn.__closure__ or ()
                 if hasattr(c.cell_contents, "lower")), None)


def compiled_texts(run) -> List[str]:
    """The compiled HLO of the engine's decode step and of its prefill
    chunk at each (rows, tokens) the traced window dispatched, from the
    process that ran them (lowered again with the arguments' shapes, the
    programs come from JAX's cache).  Nothing for an engine without
    ``_init_sub``, whose chunk arguments this cannot shape."""
    import jax
    import jax.numpy as jnp
    eng = run.rec.eng

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            tree)

    step, chunk = _jitted(eng._decode), _jitted(eng._decode_chunk)
    init_sub = getattr(eng, "_init_sub", None)
    if step is None or chunk is None or init_sub is None:
        return []
    params = shapes(eng.params)
    calls = [(step, (params, shapes(eng.cache),
                     jax.ShapeDtypeStruct((eng.n_slots, 1), jnp.int32)))]
    for rows, tokens in sorted({(d.rows, d.tokens)
                                for d in run.traced_dispatches()
                                if d.kind == "chunk"}):
        calls.append((chunk, (params, jax.eval_shape(init_sub, rows),
                              jax.ShapeDtypeStruct((rows, tokens),
                                                   jnp.int32))))
    return [fn.lower(*args).compile().as_text() for fn, args in calls]


def for_run(run, root: Path) -> Optional[dict]:
    """The reduced trace of ``run``'s cell in the checkout at ``root``,
    loaded once per trace file and reported once on stderr."""
    path = devtrace.find_xplane(trace_dir(root, run.cell["name"]))
    if path is None:
        return None
    if path not in _loaded:
        t = time.perf_counter()
        texts = compiled_texts(run)
        events = with_paths(load(path), op_paths(texts))
        s = run.spec
        reduced = reduce(events, (s.max_len, s.n_kv, s.head_dim))
        print(f"spans: {len(texts)} compiled programs named the ops; "
              f"{time.perf_counter() - t!r} s", file=sys.stderr)
        if reduced is not None:
            report(reduced)
        _loaded[path] = (events, reduced)
    return _loaded[path][1]


def traced_tokens(run) -> int:
    """Real tokens of the traced dispatches: rows × tokens per dispatch,
    as ``packed_matmul_roofline`` counts them."""
    return sum(d.rows * d.tokens for d in run.traced_dispatches())
