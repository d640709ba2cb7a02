#!/usr/bin/env python3
"""Readings that set a cell's fixed numbers, made once on the chip.

    python3 bench/calibrate.py limits --workload minicpm-2b.int4.chat \\
        --seconds 20 --seeds 101 102 ... --control-seeds 101 102 103
    python3 bench/calibrate.py sweep --workload minicpm-2b.int4.chat \\
        --seconds 51 --seeds 5 --rates 0.1 0.15 0.2

``limits`` serves each seed in one process (the engine's programs traced
once and shared), for a short window at the cell's own load, then reads
the widest reference gap of the served tokens (the lower reading of the
correctness limit) and, for the control seeds, the widest gap of the
tokens that the float8 reference puts first (the upper reading).

``sweep`` serves the open-loop mix of the cell at each rate from an idle
engine, and prints the backlog at the window's close beside the metrics
that ``bench/metrics/`` reads: the knee is the highest rate whose backlog
does not grow.

Every line printed is a JSON object; none is a benchmark result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

#: the metrics ``sweep`` reads at each rate, through their own readers
SWEPT = ("output_tok_s", "ttft_p50_ms", "queue_wait_p50_ms",
         "prefill_ms_per_tok", "decode_step_ms", "slot_occupancy")


def _serve(spec, mix, seed, seconds, jits, clock, warm):
    import harness
    from weights import program_params
    params = program_params(spec, seed)
    eng = harness.build_engine(spec, params, jits)
    jits = harness.engine_jits(eng)
    rec = harness.Recorder(eng)
    if warm:
        harness.warm(eng, spec)
    w = harness.drive(eng, rec, spec, mix, seed, seconds, clock,
                      finish=mix["check_requests"])
    rec.eng = None
    del eng, params
    gc.collect()
    return w, rec, jits


def limits(args) -> None:
    import check
    import harness
    _, _, spec, mix = harness.load_cell(args.workload)
    clock = harness.CompileClock()
    jits = None
    for seed in args.seeds:
        t = time.perf_counter()
        w, rec, jits = _serve(spec, mix, seed, args.seconds, jits, clock,
                              warm=True)
        finished = [r for r in w.arrived if r.done]
        reqs = check.sample(finished, seed, mix["check_requests"],
                            rec.slot_of)
        gaps, state = check.served_gaps(spec, seed, reqs)
        row = {"seed": seed, "requests": len(reqs), "tokens": int(gaps.size),
               "served_max_gap_sigma": float(gaps.max()),
               "served_mean_gap_sigma": float(gaps.mean()),
               "served_top1": float((gaps == 0).mean())}
        if seed in args.control_seeds:
            cg = check.control_gaps(spec, state)
            row.update(control_max_gap_sigma=float(cg.max()),
                       control_mean_gap_sigma=float(cg.mean()),
                       control_top1=float((cg == 0).mean()))
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        del state, gaps
        gc.collect()


def sweep(args) -> None:
    import jax

    import harness
    import roofline
    from run import reader
    _, cell, spec, mix = harness.load_cell(args.workload)
    peak = roofline.peaks(jax.devices()[0].device_kind)
    clock = harness.CompileClock()
    jits = None
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        w, rec, jits = _serve(spec, m, args.seeds[0], args.seconds, jits,
                              clock, warm=jits is None)
        view = harness.RunView(spec=spec, cell=cell, mix=m, window=w,
                               rec=rec, setup_s=0.0, trace=None, peak=peak)
        row = {"rate_per_s": rate, "arrived": len(w.arrived),
               "backlog_at_close": w.backlog, "drain_s": w.drain_s,
               "unanswered": w.unanswered, "compiles_in_window": w.compiles}
        for name in SWEPT:
            row[name] = reader(name)(view)
        print(json.dumps(row), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("limits", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate: needs a TPU")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    (limits if args.what == "limits" else sweep)(args)


if __name__ == "__main__":
    main()
