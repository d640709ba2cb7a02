"""Serving benchmark: weight-format ladder + scheduler comparison.

Part 1 (ladder): runs the static-batching ServeEngine (chunked prefill,
DESIGN.md §8) over the same request set with bf16, int8-code, and the
full packed sub-byte ladder (int4 nibbles / int3 bit-planes / int2
fields) and reports, per format:

  * decode tokens/s (greedy generation wall clock, per-round timing hooks),
  * prefill device calls (ceil(prompt_len/chunk) with chunking),
  * modeled HBM bytes per logical weight — the decode roofline term the
    quantized formats shrink (measured from the actual param tree via
    quant.qweight_bytes, so scale vectors and escape COO overhead count).

``--json PATH`` dumps the rows plus, per ladder format, the
engine-reported ``weight_bytes`` and the exact per-leaf storage
inventory (quant.leaf_inventory) — CI uploads the file as a workflow
artifact and ``benchmarks/check_bytes.py`` (stdlib-only) gates that the
reported bytes match the packing-layout accounting for every format.

Part 2 (scheduler): a mixed-prompt-length, mixed-budget workload with
Poisson arrivals driven through the static-rounds engine and the
continuous-batching engine (DESIGN.md §9), reporting end-to-end tokens/s
and p50/p99 TTFT.  Static rounds head-of-line-block mixed-length traffic
(each round admits one equal-length group and pays the round's max budget
in decode dispatches); continuous batching refills slots mid-flight, so
it must win tokens/s on this workload — asserted below.

Part 3 (resilience, DESIGN.md §12): the armed resilience layer (per-step
payload integrity + retry policy, no faults firing) must not change one
token, and its overhead ratio is reported; an overload burst must walk
the degradation ladder down (rung history reported) with every submitted
request accounted finished-or-dropped exactly.

Part 4 (``--quality``, DESIGN.md §14): clean vs seeded-chaos serving
cells with the quality observatory attached — streamed Σ_X divergence,
online distortion probes against the fp twin, drift/SLO verdicts — whose
summaries ``benchmarks/check_quality.py`` gates against the committed
``BENCH_serve.json`` trajectory.

Part 5 (``--requant``, DESIGN.md §15): a drift-injection cell with the
live requantization loop armed — the detector must fire exactly once,
the hot-swap must land at a step boundary with zero serving gap, and
the swapped tree must be bit-identical to an offline re-plan from the
recorded Σ snapshots; ``benchmarks/check_requant.py`` gates the summary.

CPU wall-clock is NOT the TPU story (the dry-run roofline is); the bytes
model is the hardware-portable claim.  The scheduler comparison is
dispatch-count-structural, so it survives the backend change.

With ``--trace-out``/``--metrics-out``/``--events-out`` the bench also
runs under ``repro.obs`` (DESIGN.md §11) and exports the Chrome trace,
Prometheus exposition, and JSONL metric log that
``benchmarks/check_obs.py`` audits.

    python benchmarks/serve_bench.py [--quick] \
        [--json out.json --trace-out trace.json --metrics-out m.prom]
"""
import argparse
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_schema import envelope  # noqa: E402  (shared --json header)

from repro import chaos, obs
from repro.configs.base import ArchConfig
from repro.dist.fault import RestartPolicy
from repro.launch.serve import add_obs_flags, obs_export, obs_setup
from repro.models import decode_chunk, decode_step, init_params, split_tree
from repro.quant import leaf_inventory, quantize_params_tree, qweight_bytes
from repro.serve import (ContinuousEngine, DegradePolicy, EngineConfig,
                         QualityConfig, QualityMonitor, Request,
                         ResilienceConfig, ServeEngine, build_bit_ladder)


def _engine_run(cfg, params, prompts, max_new, chunk, decode_fns=None):
    ec = EngineConfig(n_slots=len(prompts),
                      max_len=prompts[0].size + max_new + 2,
                      prefill_chunk=chunk,
                      decode_fn=decode_fns[0] if decode_fns else None,
                      decode_chunk_fn=decode_fns[1] if decode_fns else None)
    eng = ServeEngine(cfg, params, config=ec)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=max_new))
    t0 = time.perf_counter()
    done = eng.run_until_done()
    wall = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    st = eng.round_stats[0]
    return {"tok_s": toks / max(st.decode_s, 1e-9),
            "wall_s": wall, "tokens": toks,
            "prefill_calls": st.prefill_calls,
            "prefill_s": st.prefill_s,
            "weight_bytes": eng.weight_bytes,
            "weight_formats": dict(eng.weight_formats),
            "out": {r.rid: tuple(r.out_tokens) for r in done}}


# ---------------------------------------------------------------------------
# Part 1b — mesh ladder: k-sharded tensor-parallel serving (DESIGN.md §13)
# ---------------------------------------------------------------------------


def mesh_compare(rows_out, cfg, trees, prompts, max_new, chunk):
    """Serve every ladder format k-sharded over the full model axis and
    assert the mesh engine's streams are BIT-identical to the single-
    device oracle over the same sharded tree.  The ``mesh_*`` ladder
    entries carry the sharded per-leaf inventory, so check_bytes.py's
    per-shard pad accounting is exercised by the same gate as the
    single-device layouts."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve import build_sharded_decode_fns, shard_params_tree

    mesh = make_host_mesh(model_parallel=len(jax.devices()))
    shards = int(mesh.shape["model"])
    results = {}
    for name, tree in trees.items():
        sp = shard_params_tree(tree, shards)
        base = _engine_run(cfg, sp, prompts, max_new, chunk)
        fns = build_sharded_decode_fns(cfg, sp, mesh)
        res = _engine_run(cfg, sp, prompts, max_new, chunk, decode_fns=fns)
        assert res["out"] == base["out"], \
            f"mesh_{name}: sharded streams diverged from the oracle"
        res["inventory"] = leaf_inventory(sp)
        res["shards"] = shards
        _, fb = qweight_bytes(tree)             # logical (unpadded) bf16
        res["bytes_per_w"] = res["weight_bytes"] / (fb / 2)
        results[f"mesh_{name}"] = res
        rows_out.append((
            f"serve/mesh_{name}", res["tok_s"],
            f"shards={shards};tokens={res['tokens']};"
            f"hbm_bytes_per_w={res['bytes_per_w']:.3f};"
            f"wall_s={res['wall_s']:.2f};oracle_identical=1"))
    return results


# ---------------------------------------------------------------------------
# Part 2 — scheduler comparison (static rounds vs continuous batching)
# ---------------------------------------------------------------------------


def _mixed_workload(cfg, quick):
    """Mixed lengths + skewed budgets + Poisson arrivals.

    Budget skew is the static scheduler's structural weakness: each
    equal-length round pays max(budgets) decode dispatches while its short
    requests idle; continuous batching backfills those slots.
    """
    rng = np.random.default_rng(7)
    if quick:
        # every equal-length pair holds one long and one short budget, so a
        # static round always pays the long budget while its short slot idles
        plens = [4, 6, 8, 10, 4, 6, 8, 10]
        budgets = [24, 2, 24, 2, 2, 24, 2, 24]
        mean_gap_s = 0.002
    else:
        # six distinct lengths × 2 against 4 slots: static rounds can never
        # fill their batch, continuous packs slots regardless of length
        plens = [8, 10, 12, 14, 16, 18, 8, 10, 12, 14, 16, 18]
        budgets = [24, 2, 24, 2, 24, 2, 2, 24, 2, 24, 2, 24]
        mean_gap_s = 0.005
    prompts = [rng.integers(0, cfg.vocab, p).astype(np.int32) for p in plens]
    arrivals = np.cumsum(rng.exponential(mean_gap_s, len(plens)))
    return prompts, budgets, arrivals


def _drive(eng, prompts, budgets, arrivals):
    """Feed requests at their (simulated) arrival times; run to drain.

    Arrival timestamps are pinned to the simulated schedule so TTFT counts
    queue wait from the *arrival*, not from submit.
    """
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=b)
            for i, (p, b) in enumerate(zip(prompts, budgets))]
    continuous = isinstance(eng, ContinuousEngine)
    n = len(reqs)
    i = 0
    t0 = time.perf_counter()

    def busy():
        return bool(eng.queue) or (continuous and eng.active_slots > 0)

    while i < n or busy():
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            reqs[i].arrival_s = t0 + arrivals[i]
            eng.submit(reqs[i])
            i += 1
        if busy():
            eng.step() if continuous else eng.run_round()
        elif i < n:
            time.sleep(min(arrivals[i] - now, 5e-4))
    wall = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    toks = sum(len(r.out_tokens) for r in reqs)
    ttft = np.array([r.ttft_s for r in reqs])
    return {"tok_s": toks / wall, "wall_s": wall, "tokens": toks,
            "ttft_p50": float(np.percentile(ttft, 50)),
            "ttft_p99": float(np.percentile(ttft, 99)),
            "out": {r.rid: tuple(r.out_tokens) for r in reqs}}


def scheduler_compare(rows_out, cfg, params, quick=False):
    prompts, budgets, arrivals = _mixed_workload(cfg, quick)
    n_slots = 4
    max_len = max(len(p) for p in prompts) + max(budgets) + 2
    chunk = 4 if quick else 8
    # one shared pair of jitted decode fns: both schedulers (and the warmup
    # pass) reuse the same compile cache, so the timed run is compile-free
    shared = dict(
        decode_fn=jax.jit(
            lambda p, c, t: decode_step(cfg, p, c, t)),
        decode_chunk_fn=jax.jit(
            lambda p, c, tk: decode_chunk(cfg, p, c, tk)))

    ec = EngineConfig(n_slots=n_slots, max_len=max_len,
                      prefill_chunk=chunk, **shared)

    def make(cls):
        return cls(cfg, params, config=ec)

    results = {}
    for name, cls in (("static", ServeEngine),
                      ("continuous", ContinuousEngine)):
        # admission burst sizes depend on wall-clock arrival timing, so a
        # timed run can hit a prefill batch shape the warmup never
        # compiled; best-of-N absorbs that (and OS noise) for both engines
        _drive(make(cls), prompts, budgets, arrivals)          # warm compile
        res = max((_drive(make(cls), prompts, budgets, arrivals)
                   for _ in range(3)), key=lambda r: r["tok_s"])
        results[name] = res
        rows_out.append((
            f"sched/{name}", res["tok_s"],
            f"tokens={res['tokens']};wall_s={res['wall_s']:.3f};"
            f"ttft_p50_ms={res['ttft_p50']*1e3:.1f};"
            f"ttft_p99_ms={res['ttft_p99']*1e3:.1f}"))
    # both schedulers emit identical greedy token streams (differential
    # invariant) and continuous batching must beat static rounds on
    # end-to-end tokens/s for mixed-length traffic (ISSUE acceptance)
    assert results["continuous"]["out"] == results["static"]["out"]
    assert results["continuous"]["tok_s"] > results["static"]["tok_s"], \
        (results["continuous"]["tok_s"], results["static"]["tok_s"])
    results["n_slots"] = n_slots
    return results


# ---------------------------------------------------------------------------
# Part 3 — resilience: layer overhead + overload degradation (DESIGN.md §12)
# ---------------------------------------------------------------------------


def resilience_bench(rows_out, cfg, params, quick=False):
    """Two claims: (a) the armed resilience layer (deadlines + per-step
    payload integrity + retry policy, no faults firing) costs little and
    changes NO token, (b) under an overload burst the degradation policy
    walks the bit ladder down (strictly fewer weight bytes per dispatch)
    and every submitted request is accounted finished-or-dropped exactly.
    """
    rng = np.random.default_rng(11)
    n_req = 6 if quick else 10
    budget = 6 if quick else 12
    prompts = [rng.integers(0, cfg.vocab, 6).astype(np.int32)
               for _ in range(n_req)]
    max_len = 6 + budget + 2

    def serve(resilience):
        eng = ContinuousEngine(cfg, params, config=EngineConfig(
            n_slots=4, max_len=max_len, prefill_chunk=4,
            resilience=resilience))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(),
                               max_new_tokens=budget))
        t0 = time.perf_counter()
        done = eng.run_until_done()
        return eng, time.perf_counter() - t0, \
            {r.rid: tuple(r.out_tokens) for r in done}

    armed = ResilienceConfig(
        retry=RestartPolicy(max_restarts=4, reset_after=8),
        integrity_every=1)          # worst case: checksum EVERY step
    _, _, _ = serve(None)                                   # warm compile
    _, base_s, base_out = serve(None)
    eng_on, on_s, on_out = serve(armed)
    assert on_out == base_out, "armed resilience changed token streams"
    overhead = on_s / max(base_s, 1e-9)
    rows_out.append(("resil/overhead", overhead,
                     f"base_s={base_s:.3f};armed_s={on_s:.3f};"
                     f"integrity_every=1"))

    # overload burst down the ladder: rung 0 is the nominal tree, lower
    # rungs requantize it (same machinery mixed-rate serving uses)
    ladder = build_bit_ladder(params, (None, 3, 2))
    pol = DegradePolicy(ladder=ladder, high_watermark=4, low_watermark=1,
                        streak=1, cooldown_steps=2)
    eng = ContinuousEngine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=max_len, prefill_chunk=4,
        resilience=ResilienceConfig(degrade=pol, queue_cap=4 * n_req)))
    burst = 2 * n_req
    submitted = sum(
        1 for i in range(burst)
        if eng.submit(Request(rid=i,
                              prompt=prompts[i % n_req].copy(),
                              max_new_tokens=budget)))
    done = eng.run_until_done()
    down = [r for r in eng.rung_history if r[2] == "down"]
    assert down, "overload burst never degraded down the ladder"
    assert len(done) + len(eng.dropped) == submitted, "lost requests"
    rungs = " -> ".join(f"{name}@{tick}"
                        for tick, name, _ in eng.rung_history)
    rows_out.append(("resil/degrade", len(down),
                     f"rungs={rungs};finished={len(done)};"
                     f"dropped={len(eng.dropped)};submitted={submitted}"))
    return {"overhead": {"base_s": base_s, "armed_s": on_s,
                         "ratio": overhead},
            "degrade": {"rungs": [list(r) for r in eng.rung_history],
                        "down_shifts": len(down),
                        "finished": len(done),
                        "dropped": len(eng.dropped),
                        "submitted": submitted}}


# ---------------------------------------------------------------------------
# Part 4 — quality observatory (DESIGN.md §14)
# ---------------------------------------------------------------------------


def quality_bench(rows_out, cfg, params, quick=False, events_out=None):
    """Two obs-enabled serving cells over the SAME packed-int4 tree and
    workload, each with a :class:`QualityMonitor` attached: a clean run
    (zero drift flags allowed) and a chaos run with seeded slow-step +
    corrupt-payload faults (the drift detectors MUST flag both the
    ``step_s`` and ``integrity`` series).  Each cell's monitor summary —
    probe-measured vs plan-predicted per-matrix distortion, drift
    verdicts, SLO burn rates — lands in the JSON under ``quality``;
    ``benchmarks/check_quality.py`` gates the verdicts and the
    measured/predicted reconciliation band.

    Runs inside ``obs.scoped`` so the always-on sampling cannot disturb
    the surrounding run's counters.
    """
    from repro.obs.drift import Threshold
    from repro.plan.sensitivity import collect_sigma_x

    rng = np.random.default_rng(3)
    n_req, plen, budget = 4, 8, (16 if quick else 24)
    prompts = [rng.integers(0, cfg.vocab, plen).astype(np.int32)
               for _ in range(n_req)]
    calib = [jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
             for _ in range(2)]
    acc = collect_sigma_x(cfg, params, calib)
    qtree = quantize_params_tree(params, nbits=4, packed=True)
    max_len = plen + budget + 2
    # one shared pair of jitted decode fns: the warmup pass below absorbs
    # every compile, so cell step times measure dispatch, not compiles —
    # the margin the absolute step_s threshold detector relies on
    shared = dict(
        decode_fn=jax.jit(lambda p, c, t: decode_step(cfg, p, c, t)),
        decode_chunk_fn=jax.jit(lambda p, c, tk: decode_chunk(cfg, p, c,
                                                              tk)))
    qcfg = QualityConfig(
        sigma_every=2, probe_every=4, slo_every=8,
        # absolute-threshold step detector: a clean warmed step on this
        # model is O(ms); the chaos sleep is 0.5 s — two orders of margin
        # on both sides keeps BOTH cell verdicts deterministic
        detectors={"step_s": lambda: Threshold(limit=0.25),
                   "integrity": lambda: Threshold(limit=0.0)},
        track_sigma_drift=False)    # live traffic != calib tokens by design

    def cell(plan):
        with obs.scoped(enable_obs=True):
            mon = QualityMonitor(cfg, params, calib=acc, config=qcfg)
            eng = ContinuousEngine(cfg, qtree, config=EngineConfig(
                n_slots=n_req, max_len=max_len, prefill_chunk=4,
                quality=mon,
                resilience=ResilienceConfig(integrity_every=1), **shared))
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, prompt=p.copy(),
                                   max_new_tokens=budget))
            if plan is not None:
                with chaos.active(plan):
                    done = eng.run_until_done()
            else:
                done = eng.run_until_done()
            assert len(done) == n_req and not eng.dropped
            summary = mon.summary()
            summary["out"] = {r.rid: list(map(int, r.out_tokens))
                              for r in done}
            if plan is not None and events_out:
                obs.write_jsonl(events_out)
            return summary

    # warm every decode/prefill shape fault-free before either timed cell
    warm = ContinuousEngine(cfg, qtree, config=EngineConfig(
        n_slots=n_req, max_len=max_len, prefill_chunk=4, **shared))
    for i, p in enumerate(prompts):
        warm.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=budget))
    warm.run_until_done()

    clean = cell(None)
    sp = chaos.seeded_plan("slow-step", seed=0, horizon=12, n_faults=2,
                           first=2, delay_s=0.5)
    cp = chaos.seeded_plan("corrupt-payload", seed=0, horizon=12,
                           n_faults=2, first=2, n_bytes=3)
    chaotic = cell(chaos.ChaosPlan(seed=0, specs=sp.specs + cp.specs))

    # the chaos cell serves the same greedy streams (faults heal), the
    # clean cell stays silent, and the chaos cell flags BOTH series
    assert chaotic["out"] == clean["out"], \
        "chaos cell changed token streams despite healing"
    assert clean["drift"]["n_flags"] == 0, \
        f"clean cell flagged drift: {clean['drift']}"
    flagged = chaotic["drift"]["series"]
    assert flagged.get("step_s", 0) >= 1, f"slow-step not flagged: {flagged}"
    assert flagged.get("integrity", 0) >= 1, \
        f"corrupt-payload not flagged: {flagged}"
    rows_out.append(("quality/clean", clean["n_probes"],
                     f"ticks={clean['ticks']};flags=0;"
                     f"logits_mse={clean['logits_mse_mean']:.3e}"))
    rows_out.append(("quality/chaos", chaotic["drift"]["n_flags"],
                     f"ticks={chaotic['ticks']};"
                     f"step_s_flags={flagged.get('step_s', 0)};"
                     f"integrity_flags={flagged.get('integrity', 0)}"))
    return {"clean": clean, "chaos": chaotic}


# ---------------------------------------------------------------------------
# Part 5 — live requantization under drift (DESIGN.md §15)
# ---------------------------------------------------------------------------


def requant_bench(rows_out, cfg, params, quick=False):
    """One obs-enabled serving cell with the full sense→decide→act loop
    armed: clean traffic, then a rank-collapsing repeated-token phase
    that trips the streamed-Σ frobenius detectors.  The actuator must
    fire EXACTLY once, re-solve the affected matrices over the residual
    budget, and hot-swap at a step boundary with zero serving gap (every
    busy scheduler step emits tokens, asserted per-step).  The summary
    carries the per-step emission log, the offline bit-identity verdict
    (re-running the pure re-plan from the recorded Σ snapshots must land
    the byte-identical tree), and the post-swap realized/predicted
    distortion ratios — ``benchmarks/check_requant.py`` gates all of it.
    """
    from repro.plan import build_plan, collect_sigma_x, model_sensitivities
    from repro.quant.pipeline import matrix_tap_map
    from repro.serve import (EngineConfig, RequantConfig, engine_from_plan,
                             replan_from_sigma, sigma_threshold_detectors)

    rng = np.random.default_rng(9)
    plen, budget = 8, 8
    calib = [rng.integers(0, cfg.vocab, (2, 16)).astype(np.int32)
             for _ in range(2)]
    sens = model_sensitivities(cfg, params, calib, weighting="output")
    plan = build_plan(sens, 4.0, weighting="output")
    acc = collect_sigma_x(cfg, params, calib)
    # threshold calibrated on this workload: steady-state clean shift sits
    # near 1.0 (serving traffic != calib tokens), the repeated-token phase
    # pushes every tap past 2.3 once its samples dominate the stream
    qcfg = QualityConfig(
        sigma_every=1, probe_every=10_000, slo_every=10_000,
        detectors=sigma_threshold_detectors(matrix_tap_map(cfg, params),
                                            limit=2.0))
    with obs.scoped(enable_obs=True):
        eng = engine_from_plan(
            cfg, params, plan, calib=acc, sensitivities=sens,
            quality_config=qcfg,
            config=EngineConfig(
                n_slots=2, max_len=plen + budget + 2,
                requant=RequantConfig(min_samples=8, cooldown_steps=8,
                                      max_actuations=1)))
        rid = 0

        def drive(prompt_fn, n_req, n_steps):
            nonlocal rid
            for _ in range(n_req):
                eng.submit(Request(rid=rid, prompt=prompt_fn(),
                                   max_new_tokens=budget))
                rid += 1
            for _ in range(n_steps):
                eng.step()

        drive(lambda: rng.integers(0, cfg.vocab, plen).astype(np.int32),
              6, 40)
        drive(lambda: np.full(plen, 7, np.int32), 10, 80)
    # per-step emission log (ticks are 1-based and sequential)
    steps = [{"tick": i + 1, "active": st.active, "admitted": st.admitted,
              "new_tokens": st.new_tokens}
             for i, st in enumerate(eng.step_stats)]
    acts = eng.requant.actuations
    assert len(acts) == 1, f"expected exactly 1 actuation, got {len(acts)}"
    a = acts[0]
    # offline replay of the pure re-plan from the recorded snapshots —
    # the served tree after the swap must be BYTE-identical to it
    _, tree, _, _, _ = replan_from_sigma(cfg, params, a["plan_before"],
                                         a["snapshots"])
    bit_identical = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(eng.params), jax.tree.leaves(tree)))
    swap_tick = next(t for t, why in eng.swap_history if why == "requant")
    ratios = {}
    for name in a["matrices"]:
        e = a["plan_after"].entry(name)
        if e.realized_distortion and e.pred_distortion:
            ratios[name] = e.realized_distortion / e.pred_distortion
    busy = [s for s in steps if s["active"] or s["admitted"]]
    stalled = [s["tick"] for s in busy if s["new_tokens"] < 1]
    dropped = sum(1 for r in eng.finished if r.dropped)
    summary = {
        "actuations": len(acts),
        "tick": a["tick"], "swap_tick": swap_tick,
        "taps": list(a["taps"]), "matrices": list(a["matrices"]),
        "payload_before": a["payload_before"],
        "payload_after": a["payload_after"],
        "bit_identical": bool(bit_identical),
        "busy_steps": len(busy), "stalled_steps": stalled,
        "finished": len(eng.finished), "dropped": dropped,
        "realized_over_pred": ratios,
        "replan_wall_s": a["wall_s"],
        "weight_formats_after": dict(eng.weight_formats)}
    rows_out.append(("requant/actuation", len(acts),
                     f"tick={a['tick']};swap_tick={swap_tick};"
                     f"matrices={len(a['matrices'])};"
                     f"bit_identical={int(bit_identical)};"
                     f"stalled={len(stalled)};dropped={dropped}"))
    return summary


def run(rows_out, quick=False, mesh=False, quality=False,
        quality_events_out=None, requant=False):
    cfg = ArchConfig(name="bench", family="dense",
                     n_layers=2 if quick else 4,
                     d_model=128 if quick else 256, n_heads=4, n_kv=4,
                     d_ff=256 if quick else 512, vocab=256,
                     head_dim=32 if quick else 64)
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    n_req = 2 if quick else 4
    plen = 8 if quick else 16
    max_new = 4 if quick else 16
    chunk = 4 if quick else 8
    prompts = [rng.integers(0, cfg.vocab, plen).astype(np.int32)
               for _ in range(n_req)]

    trees = {
        "bf16": params,
        "int8": quantize_params_tree(params),
        "int4_packed": quantize_params_tree(params, nbits=4, packed=True),
        "int3_packed": quantize_params_tree(params, nbits=3),
        "int2_packed": quantize_params_tree(params, nbits=2),
    }
    results = {}
    for name, tree in trees.items():
        _, fb = qweight_bytes(tree)
        n_weights = fb / 2                      # logical bf16 elements
        res = _engine_run(cfg, tree, prompts, max_new, chunk)
        # engine-reported bytes feed the headline ratio; check_bytes.py
        # independently re-derives them from the inventory's layout math
        res["bytes_per_w"] = res["weight_bytes"] / n_weights
        res["inventory"] = leaf_inventory(tree)
        results[name] = res
        rows_out.append((
            f"serve/{name}", res["tok_s"],
            f"tokens={res['tokens']};prefill_calls={res['prefill_calls']};"
            f"hbm_bytes_per_w={res['bytes_per_w']:.3f};"
            f"wall_s={res['wall_s']:.2f}"))
    # invariants the smoke run enforces: chunked dispatch count and the
    # strictly-shrinking bytes/weight ladder bf16 > int8 > packed-int4
    # > int3 > int2 (the full 2–8 bit serving ladder, DESIGN.md §8)
    assert results["bf16"]["prefill_calls"] == -(-plen // chunk)
    assert (results["int2_packed"]["bytes_per_w"]
            < results["int3_packed"]["bytes_per_w"]
            < results["int4_packed"]["bytes_per_w"]
            < results["int8"]["bytes_per_w"] < 2.0)
    if mesh:
        results.update(mesh_compare(rows_out, cfg, trees, prompts, max_new,
                                    chunk))
    results["sched"] = scheduler_compare(rows_out, cfg, params, quick=quick)
    results["resilience"] = resilience_bench(rows_out, cfg, params,
                                             quick=quick)
    if quality:
        results["quality"] = quality_bench(rows_out, cfg, params,
                                           quick=quick,
                                           events_out=quality_events_out)
    if requant:
        results["requant"] = requant_bench(rows_out, cfg, params,
                                           quick=quick)
    return results


def _json_payload(rows, results):
    """JSON-able snapshot in the shared bench envelope (bench_schema.py):
    ladder formats carry the engine-reported bytes and the per-leaf
    storage inventory check_bytes.py audits; an optional ``quality``
    block carries the monitor summaries check_quality.py gates."""
    ladder = {}
    for name, res in results.items():
        if name in ("sched", "resilience", "quality", "requant"):
            continue
        ladder[name] = {
            "tok_s": res["tok_s"], "tokens": res["tokens"],
            "bytes_per_w": res["bytes_per_w"],
            "weight_bytes": res["weight_bytes"],
            "weight_formats": res["weight_formats"],
            "inventory": res["inventory"]}
    payload = envelope("serve")
    payload.update({"rows": [list(r) for r in rows], "ladder": ladder,
                    "sched": {"n_slots": results["sched"]["n_slots"]},
                    "resilience": results["resilience"]})
    if "quality" in results:
        payload["quality"] = results["quality"]
    if "requant" in results:
        payload["requant"] = results["requant"]
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny model / few requests (CI smoke)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write rows + per-format storage inventory as "
                         "JSON (CI artifact; input to check_bytes.py)")
    ap.add_argument("--mesh", action="store_true",
                    help="also serve every format k-sharded over the full "
                         "model axis, asserted bit-identical to the "
                         "single-device oracle (DESIGN.md §13)")
    ap.add_argument("--quality", action="store_true",
                    help="also run the quality-observatory cells (clean + "
                         "seeded-chaos, DESIGN.md §14) and embed the "
                         "monitor summaries for check_quality.py")
    ap.add_argument("--quality-events-out", metavar="PATH", default=None,
                    help="JSONL metric log of the chaos quality cell "
                         "(input to launch/summarize.py --metrics)")
    ap.add_argument("--requant", action="store_true",
                    help="also run the live-requantization drift cell "
                         "(DESIGN.md §15) and embed its summary for "
                         "check_requant.py")
    add_obs_flags(ap)
    args = ap.parse_args()
    obs_setup(args)
    rows = []
    results = run(rows, quick=args.quick, mesh=args.mesh,
                  quality=args.quality,
                  quality_events_out=args.quality_events_out,
                  requant=args.requant)
    for r in rows:
        print(",".join(str(x) for x in r))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(_json_payload(rows, results), f, indent=1,
                      sort_keys=True, default=float)
        print(f"wrote {args.json}")
    obs_export(args)
