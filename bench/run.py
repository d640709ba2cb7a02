#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload minicpm-2b.int4.chat --seed 7 \\
        --seconds 51 --trace 0

The cell (``BENCHMARK.json``) names a configuration (``bench/configs/``)
and a traffic mix (``bench/traffic/``).  The run makes the packed-int4
weights on the device from the seed, builds the program's
``ContinuousEngine``, warms every shape the window can produce, serves the
mix for ``--seconds``, and checks a sample of what it served against the
plain reference.  ``--trace 1`` also profiles a few seconds in the middle
of the window and reports the cell's per-layer metrics instead of its
end-to-end ones.

The run exits non-zero, with no result line, when JAX's first device is
not a TPU or there are fewer devices than the cell asks for.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
last ``check``, the numbers compared beside their limits; the same
numbers close standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def reader(name: str, bench_dir: Path = BENCH):
    """``bench/metrics/<name>.py``'s ``read``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, workload: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def main(argv=None) -> int:
    args = parse(argv)
    import jax
    devices = jax.devices()
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench_json["workloads"]}.get(args.workload)
    if cell is None:
        print(f"run: no workload {args.workload!r}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"run: needs a TPU; JAX's first device is "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"run: the cell asks for {cell['chips']} chips; JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    from harness import log
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(args, bench_json, devices)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(args, bench_json, devices, root: Path = ROOT) -> dict:
    """Set up, serve and check one cell of the checkout at ``root``; the
    result line as a dict."""
    import jax

    import check
    import harness
    import roofline
    from weights import program_params
    from harness import log

    clock = harness.CompileClock()
    _, cell, spec, mix = harness.load_cell(args.workload, root)
    dev = devices[0]
    peak = roofline.peaks(dev.device_kind)
    t = time.perf_counter()
    params = program_params(spec, args.seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t
    eng = harness.build_engine(spec, params)
    rec = harness.Recorder(eng)
    t = time.perf_counter()
    harness.warm(eng, spec)
    t_warm = time.perf_counter() - t
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s!r}: weights {t_weights!r} s, warm-up {t_warm!r} "
        f"s; compile {clock.seconds!r} s over {clock.compiles} compiles")
    trace_dir = None
    if args.trace:
        trace_dir = root / ".bench_out" / f"trace-{args.workload}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = harness.drive(eng, rec, spec, mix, args.seed, args.seconds, clock,
                      trace_dir, finish=mix["check_requests"])
    peak_bytes = harness.peak_bytes()
    late = sorted(w.lateness_s)
    log(f"window: {len(w.arrived)} requests arrived, {len(w.steps)} steps, "
        f"{w.compiles} compiles and {w.lowerings} lowerings inside, "
        f"generator lateness p50 "
        f"{late[len(late) // 2] if late else 0.0!r} s max "
        f"{late[-1] if late else 0.0!r} s; the profiler held the loop "
        f"{w.held_s!r} s, left out of the window")
    finished = [r for r in w.arrived if r.done]
    reduced = None
    if trace_dir is not None:
        import devtrace
        path = devtrace.find_xplane(trace_dir)
        reduced = devtrace.reduce(devtrace.load(path)) if path else None
    run = harness.RunView(spec=spec, cell=cell, mix=mix, window=w, rec=rec,
                          setup_s=setup_s, trace=reduced, peak=peak)
    metrics = {}
    for m in metrics_for(bench_json, args.workload, bool(args.trace)):
        value = reader(m["name"], root / "bench")(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # the program's state goes before the reference runs on the chip
    rec.eng = run = None
    del eng, params
    gc.collect()
    t = time.perf_counter()
    verdict = check.judge(spec, args.seed, finished,
                          mix["check_requests"], rec.slot_of)
    log(f"reference {time.perf_counter() - t!r} s")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": verdict["correct"] and w.unanswered == 0,
              "attempted": len(w.arrived), "failed": w.unanswered,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    log(f"compared {verdict['tokens']} served tokens of "
        f"{verdict['requests']} finished requests with the reference")
    result["check"] = {
        "max_gap_sigma": {"value": verdict["max_gap_sigma"],
                          "limit": verdict["limit"]},
        "unanswered": {"value": w.unanswered, "limit": 0},
        "bad_tokens": {"value": verdict["bad_tokens"], "limit": 0}}
    for name, v in result["check"].items():
        log(f"check {name} {v['value']!r} limit {v['limit']!r}")
    return result


if __name__ == "__main__":
    sys.exit(main())
