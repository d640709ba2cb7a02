"""CI observability gate: Chrome trace + Prometheus exposition
(DESIGN.md §11).

Stdlib-only (no jax / no repro import) audit of the artifacts an
obs-enabled ``serve_bench.py --quick --json .. --trace-out ..
--metrics-out .. [--events-out ..]`` run writes:

1. **Chrome trace**: the file is valid trace-event JSON (``traceEvents``
   list, complete events carry ``ph:"X"``/``ts``/``dur``, instants
   ``ph:"i"``), events are ts-sorted, and — the scheduling claim — the
   continuous engine emitted admission (``serve.admit``), prefill
   (``serve.prefill``), and decode (``serve.decode``) spans covering
   EVERY slot of the scheduler-comparison workload (``sched.n_slots``
   from the bench JSON).  A slot that never traced would mean the
   engine's per-slot lanes are lying about occupancy.

2. **Prometheus exposition**: every sample line parses, every family has
   exactly one ``# TYPE`` header, counters end ``_total`` with
   non-negative finite values, and histograms export the summary shape
   (``quantile`` samples plus ``_sum``/``_count``).

3. **JSONL metric log** (optional): every record parses and names its
   kind; histograms carry their quantiles.

    python benchmarks/check_obs.py --bench b.json --trace t.json \
        --prom m.prom [--events e.jsonl]
"""
import argparse
import json
import math
import re

_PROM_SAMPLE = re.compile(r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
                          r'(\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$')
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _parse_labels(s):
    return {m.group(1): m.group(2).replace('\\"', '"').replace("\\\\", "\\")
            for m in _LABEL.finditer(s or "")}


# ---------------------------------------------------------------------------
# 1. Chrome trace
# ---------------------------------------------------------------------------


def check_trace(path, n_slots):
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        raise SystemExit(f"trace: {path} has no traceEvents list")
    last_ts = -1.0
    covered = {"serve.admit": set(), "serve.prefill": set(),
               "serve.decode": set()}
    for ev in events:
        for field in ("name", "ph", "ts", "pid", "tid"):
            if field not in ev:
                raise SystemExit(f"trace: event missing {field!r}: {ev}")
        if ev["ph"] not in ("X", "i"):
            raise SystemExit(f"trace: unexpected phase {ev['ph']!r}")
        if ev["ph"] == "X" and ev.get("dur", -1) < 0:
            raise SystemExit(f"trace: complete event without dur: {ev}")
        if ev["ts"] < last_ts:
            raise SystemExit("trace: events not sorted by ts")
        last_ts = ev["ts"]
        args = ev.get("args", {})
        if ev["name"] in covered and args.get("engine") == "continuous":
            if "slot" in args:
                covered[ev["name"]].add(int(args["slot"]))
            for s in args.get("slots", []):
                covered[ev["name"]].add(int(s))
    want = set(range(n_slots))
    for name, slots in sorted(covered.items()):
        missing = want - slots
        if missing:
            raise SystemExit(f"trace: {name} spans never covered slots "
                             f"{sorted(missing)} (n_slots={n_slots})")
    print(f"  trace: {len(events)} events, admit/prefill/decode spans "
          f"cover all {n_slots} slots")


# ---------------------------------------------------------------------------
# 2. Prometheus exposition
# ---------------------------------------------------------------------------


def check_prometheus(path):
    types = {}
    seen = set()
    samples = 0
    with open(path) as f:
        for ln in f:
            ln = ln.rstrip("\n")
            if not ln:
                continue
            if ln.startswith("# TYPE "):
                _, _, name, kind = ln.split(" ", 3)
                if name in types:
                    raise SystemExit(f"prom: duplicate TYPE for {name}")
                if kind not in ("counter", "gauge", "summary"):
                    raise SystemExit(f"prom: unknown kind {kind!r}")
                types[name] = kind
                continue
            if ln.startswith("#"):
                continue
            m = _PROM_SAMPLE.match(ln)
            if not m:
                raise SystemExit(f"prom: unparseable sample line: {ln!r}")
            samples += 1
            name, value = m.group("name"), float(m.group("value"))
            seen.add(name)
            base = re.sub(r"_(sum|count)$", "", name)
            if name not in types and base not in types:
                raise SystemExit(f"prom: sample {name} has no TYPE header")
            kind = types.get(name, types.get(base))
            if kind == "counter":
                if not name.endswith("_total"):
                    raise SystemExit(f"prom: counter {name} missing _total")
                if not (value >= 0 and math.isfinite(value)):
                    raise SystemExit(f"prom: counter {name} value {value}")
            if kind == "summary" and name == base:
                labels = _parse_labels(m.group("labels"))
                if "quantile" not in labels:
                    raise SystemExit(f"prom: summary sample without "
                                     f"quantile label: {ln!r}")
    for name, kind in types.items():
        # the summary shape is only complete with _sum and _count samples
        if kind == "summary" and not {f"{name}_sum",
                                      f"{name}_count"} <= seen:
            raise SystemExit(f"prom: summary {name} missing _sum/_count")
    if not samples:
        raise SystemExit(f"prom: {path} has no samples")
    print(f"  prom: {samples} samples across {len(types)} families parse")
    return types


# ---------------------------------------------------------------------------
# 3. JSONL metric log (optional)
# ---------------------------------------------------------------------------


def check_events(path):
    n = 0
    with open(path) as f:
        for ln in f:
            if not ln.strip():
                continue
            rec = json.loads(ln)
            for field in ("name", "kind"):
                if field not in rec:
                    raise SystemExit(f"events: record missing {field!r}: "
                                     f"{rec}")
            if rec["kind"] == "histogram" and "quantiles" not in rec:
                raise SystemExit(f"events: histogram without quantiles: "
                                 f"{rec}")
            n += 1
    if not n:
        raise SystemExit(f"events: {path} is empty")
    print(f"  events: {n} JSONL records parse")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", required=True,
                    help="serve_bench.py --json artifact")
    ap.add_argument("--trace", required=True, help="--trace-out artifact")
    ap.add_argument("--prom", required=True, help="--metrics-out artifact")
    ap.add_argument("--events", default=None, help="--events-out artifact")
    args = ap.parse_args(argv)
    with open(args.bench) as f:
        n_slots = json.load(f)["sched"]["n_slots"]
    check_trace(args.trace, n_slots)
    check_prometheus(args.prom)
    if args.events:
        check_events(args.events)
    print("check_obs: OK")


if __name__ == "__main__":
    main()
