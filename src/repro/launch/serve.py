"""Serving driver: batched engine on the host mesh, optionally with
WaterSIC-quantized weights — int8 codes or any rung of the packed
sub-byte ladder (int4 nibbles / int3 bit-planes / int2 fields, planar
payload + escape COO, DESIGN.md §8) via ``--wbits {16,8,4,3,2}``.

``--continuous`` swaps the static-rounds scheduler for the
continuous-batching engine (per-slot decode streams with in-flight
admission, DESIGN.md §9); the static path stays the default and the
differential reference.

``--trace-out``/``--metrics-out``/``--events-out`` enable ``repro.obs``
(DESIGN.md §11) and export the run's Perfetto-loadable Chrome trace,
Prometheus text exposition, and JSONL metric log (the input to
``launch/summarize.py --metrics``).

Resilience flags (DESIGN.md §12) attach the serving-resilience layer:
``--deadline-s``/``--queue-cap`` bound latency and queue growth (dropped
requests are reported at exit), ``--retries`` arms transient-dispatch
retry, ``--integrity-every`` checksums+heals the quantized payloads,
``--degrade`` walks the int4→int3→int2 ladder under queue pressure, and
``--snapshot-dir``/``--snapshot-every`` write crash-recoverable engine
snapshots (``--resume`` restarts from the latest one).

``--requant`` (DESIGN.md §15) serves from a waterfilled plan instead of
``--wbits`` and arms the live sense→decide→act loop: the quality
observatory streams Σ_X from traffic, and when divergence crosses
``--requant-limit`` the actuator re-solves the affected matrices over
the residual budget and hot-swaps the tree at a step boundary.  The
driver sends a drifted second traffic phase (repeated-token prompts) so
the loop demonstrably closes.  Requires ``--continuous``; incompatible
with ``--degrade`` (both subsystems hot-swap the served tree).

All engines are built from ONE :class:`repro.serve.EngineConfig` —
this driver is the reference for the config-first construction API.
Weights are initialized in bfloat16 (norm scales stay f32) and the KV
cache is bfloat16, so ``--wbits 16`` serves genuine bf16; the quantized
rungs are built from the same bf16 tree.

    PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --reduced \
        --requests 6 --wbits 4 --prefill-chunk 8 --continuous \
        --trace-out /tmp/serve_trace.json --metrics-out /tmp/serve.prom
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs
from repro.configs import get_config
from repro.dist.fault import RestartPolicy
from repro.dist.sharding import use_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import init_params, split_tree
from repro.quant import quantize_params_tree, qweight_bytes
from repro.serve import (ContinuousEngine, DegradePolicy, EngineConfig,
                         QualityConfig, Request, RequantConfig,
                         ResilienceConfig, ServeEngine, build_bit_ladder,
                         build_sharded_decode_fns, engine_from_plan,
                         integer_allgathers, lower_decode_hlo,
                         params_pspecs, shard_params_tree,
                         sigma_threshold_detectors)

#: weight and KV-cache dtype of every engine this driver builds
SERVE_DTYPE = jnp.bfloat16


def serving_params(cfg):
    """The driver's master weights: bf16 matmul weights and embedding,
    f32 norm scales, from the fixed seed 0."""
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0),
                                       SERVE_DTYPE))
    return params


def add_obs_flags(ap: argparse.ArgumentParser) -> None:
    """The shared observability exports (serve + plan drivers)."""
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON (Perfetto)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the Prometheus text exposition")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="write the JSONL metric log "
                         "(launch/summarize.py --metrics)")


def obs_setup(args) -> bool:
    """Enable repro.obs when any export flag is set; returns enablement."""
    if args.trace_out or args.metrics_out or args.events_out:
        obs.enable()
    return obs.enabled()


def obs_export(args) -> None:
    for path, write in ((args.trace_out, obs.write_trace),
                        (args.metrics_out, obs.write_prometheus),
                        (args.events_out, obs.write_jsonl)):
        if path:
            write(path)
            print(f"wrote {path}")


def add_resilience_flags(ap: argparse.ArgumentParser) -> None:
    """Serving-resilience knobs (shared with launch/chaos.py)."""
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (monotonic seconds from "
                         "arrival); expired requests are dropped, reported")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="bounded admission queue; submits past the cap "
                         "are shed")
    ap.add_argument("--retries", type=int, default=0,
                    help="transient-dispatch restart budget (0 = fail fast)")
    ap.add_argument("--retry-backoff-s", type=float, default=0.05)
    ap.add_argument("--integrity-every", type=int, default=None, metavar="N",
                    help="checksum the quantized payloads every N steps "
                         "and heal corruption from pristine copies")
    ap.add_argument("--degrade", action="store_true",
                    help="walk the serving bit ladder down under queue "
                         "pressure (and back up when it drains)")
    ap.add_argument("--degrade-high", type=int, default=8,
                    help="queue depth that counts as overload")
    ap.add_argument("--degrade-low", type=int, default=1,
                    help="queue depth that counts as drained")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="periodic engine snapshots via dist.checkpoint")
    ap.add_argument("--snapshot-every", type=int, default=16, metavar="N")
    ap.add_argument("--resume", action="store_true",
                    help="resume the continuous engine from the latest "
                         "snapshot in --snapshot-dir")


def resilience_from_args(args, params) -> ResilienceConfig | None:
    """Build the ResilienceConfig the flags describe (None if untouched).

    ``params`` is the engine's nominal serving tree — with ``--degrade``
    it becomes rung 0 of the ladder and the lower rungs are quantized
    down from it via the usual machinery.
    """
    degrade = None
    if args.degrade:
        # nominal tree first; lower rungs re-quantize the same leaves
        # down the ladder (already-int4 rung 0 keeps its packed leaves:
        # quantize_params_tree passes qweight nodes through unchanged)
        degrade = DegradePolicy(
            ladder=[("rung0", params)] + build_bit_ladder(params, (3, 2)),
            high_watermark=args.degrade_high,
            low_watermark=args.degrade_low)
    retry = RestartPolicy(max_restarts=args.retries,
                          backoff_base_s=args.retry_backoff_s,
                          reset_after=4) if args.retries else None
    if not any([args.deadline_s, args.queue_cap, retry,
                args.integrity_every, degrade, args.snapshot_dir]):
        return None
    return ResilienceConfig(
        queue_cap=args.queue_cap,
        default_deadline_s=args.deadline_s,
        retry=retry,
        integrity_every=args.integrity_every,
        degrade=degrade,
        snapshot_dir=args.snapshot_dir,
        snapshot_every=args.snapshot_every if args.snapshot_dir else None)


def add_requant_flags(ap: argparse.ArgumentParser) -> None:
    """Live-requantization knobs (DESIGN.md §15)."""
    g = ap.add_argument_group("requant")
    g.add_argument("--requant", action="store_true",
                   help="serve from a waterfilled plan and re-plan + "
                        "hot-swap live when traffic Σ drifts (needs "
                        "--continuous; incompatible with --degrade)")
    g.add_argument("--requant-budget", type=float, default=4.0,
                   help="global bit budget per param for the plan")
    g.add_argument("--requant-calib", type=int, default=2, metavar="N",
                   help="synthetic calibration batches for the initial plan")
    g.add_argument("--requant-limit", type=float, default=2.0,
                   help="sigma_fro divergence threshold arming the drift "
                        "detectors (relative Frobenius shift)")
    g.add_argument("--requant-min-samples", type=int, default=32)
    g.add_argument("--requant-cooldown", type=int, default=8)
    g.add_argument("--requant-max", type=int, default=None, metavar="K",
                   help="cap on actuations (default unbounded)")
    g.add_argument("--requant-sigma-every", type=int, default=2,
                   help="shadow Σ_X sampling period (engine ticks)")


def requant_from_args(args) -> RequantConfig | None:
    if not args.requant:
        return None
    return RequantConfig(min_samples=args.requant_min_samples,
                         cooldown_steps=args.requant_cooldown,
                         max_actuations=args.requant_max)


def _requant_engine(args, cfg, params, econfig):
    """Plan-driven engine with the live requant loop armed (§15)."""
    from repro.plan import build_plan, collect_sigma_x, model_sensitivities
    from repro.quant.pipeline import matrix_tap_map

    rng = np.random.default_rng(1)
    calib = [rng.integers(0, cfg.vocab,
                          (2, max(args.prompt_len, 8))).astype(np.int32)
             for _ in range(args.requant_calib)]
    sens = model_sensitivities(cfg, params, calib, weighting="output")
    plan = build_plan(sens, args.requant_budget, weighting="output")
    acc = collect_sigma_x(cfg, params, calib)
    qc = QualityConfig(
        sigma_every=args.requant_sigma_every,
        detectors=sigma_threshold_detectors(
            matrix_tap_map(cfg, params), limit=args.requant_limit))
    eng = engine_from_plan(cfg, params, plan, calib=acc,
                           sensitivities=sens, config=econfig,
                           continuous=True, quality_config=qc)
    print(f"requant armed: {plan.planned_bits_per_param:.2f} b/param plan, "
          f"limit={args.requant_limit} "
          f"cooldown={args.requant_cooldown} "
          f"min_samples={args.requant_min_samples}")
    return eng, plan


def _quantize_for_wbits(params, wbits: int):
    if wbits == 8:
        params = quantize_params_tree(params)
        print("serving int8 WaterSIC-code weights")
    elif wbits == 4:
        params = quantize_params_tree(params, nbits=4, packed=True)
        print("serving packed-int4 WaterSIC-code weights (planar nibble "
              "payload, fused unpack kernel)")
    elif wbits == 3:
        params = quantize_params_tree(params, nbits=3)
        print("serving int3 WaterSIC-code weights (bit-plane payload, "
              "in-kernel plane unpack)")
    elif wbits == 2:
        params = quantize_params_tree(params, nbits=2)
        print("serving int2 WaterSIC-code weights (planar 2-bit fields, "
              "4 codes/byte, in-kernel shift/mask unpack)")
    if wbits != 16:
        qb, fb = qweight_bytes(params)
        print(f"  param bytes {qb/1e6:.2f} MB vs bf16 {fb/1e6:.2f} MB "
              f"({fb/max(qb,1):.2f}x HBM win)")
    return params


def main_mesh(args, cfg):
    """Tensor-parallel k-sharded serving (DESIGN.md §13).

    Shards the serving tree over the full ``model`` axis, runs the SAME
    sharded tree through (a) the single-device oracle engine and (b) the
    mesh engine (whole decode step under one shard_map), and asserts the
    token streams are bit-identical.  ``--mesh-json`` dumps streams,
    per-leaf storage inventory, and the decode HLO's collective audit for
    the stdlib ``benchmarks/check_mesh.py`` gate.
    """
    import json

    from repro.models.transformer import init_cache
    from repro.quant import leaf_format_histogram, leaf_inventory

    # NOTE: the oracle runs OUTSIDE any use_mesh context — a partitioned
    # single-host graph could reassociate reductions; the oracle must be
    # the plain single-device program over the sharded tree.
    mesh = make_host_mesh(model_parallel=len(jax.devices()))
    shards = int(mesh.shape["model"])
    params = _quantize_for_wbits(serving_params(cfg), args.wbits)
    params = shard_params_tree(params, shards)
    qb, _ = qweight_bytes(params)
    print(f"mesh serving: {shards}-way in-feature sharding on {mesh} "
          f"({qb/1e6:.2f} MB stored, per-shard pad included)")
    # the mesh engine's copy is placed on the mesh once, so no dispatch
    # moves weight shards between devices; the oracle keeps the tree on
    # the default device
    placed = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), params_pspecs(params),
        is_leaf=lambda x: isinstance(x, PartitionSpec)))
    max_len = args.prompt_len + args.max_new + 2
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32)
               for _ in range(args.requests)]

    base = EngineConfig(n_slots=args.slots, max_len=max_len,
                        cache_dtype=SERVE_DTYPE,
                        prefill_chunk=args.prefill_chunk or None,
                        resilience=resilience_from_args(args, params))

    def serve(tree, decode_fns, tag):
        econfig = base
        if decode_fns is not None:
            econfig = dataclasses.replace(base, decode_fn=decode_fns[0],
                                          decode_chunk_fn=decode_fns[1])
        cls = ContinuousEngine if args.continuous else ServeEngine
        eng = cls(cfg, tree, config=econfig)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(),
                               max_new_tokens=args.max_new))
        t0 = time.perf_counter()
        done = eng.run_until_done()
        dt = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
        print(f"  {tag}: {len(done)} requests, {toks} tokens in {dt:.2f}s")
        return eng, {r.rid: list(r.out_tokens) for r in done}

    oracle_eng, oracle = serve(params, None, "single-device oracle")
    fns = build_sharded_decode_fns(cfg, placed, mesh)
    mesh_eng, meshed = serve(placed, fns, f"{shards}-shard mesh")
    identical = oracle == meshed
    print(f"  streams bit-identical: {identical}")
    for rid in sorted(oracle):
        a, b = oracle[rid], meshed.get(rid, [])
        at = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                  None if len(a) == len(b) else min(len(a), len(b)))
        if at is not None:
            print(f"  first divergence: rid={rid} token {at} "
                  f"(oracle {a[at:at + 4]} vs mesh {b[at:at + 4]})")
            break
    probe = np.stack(prompts[:1])
    dl = np.abs(np.asarray(oracle_eng.prefill_logits(probe), np.float32)
                - np.asarray(mesh_eng.prefill_logits(probe), np.float32))
    print(f"  prefill logits max |oracle - mesh| = {float(dl.max())!r}")

    # collective audit: NO integer (weight-payload) all-gather may appear
    # on the compiled decode path — weights stay put, activations move
    cache = init_cache(cfg, args.slots, max_len, SERVE_DTYPE,
                       per_slot=args.continuous)
    tok = jnp.zeros((args.slots, 1), jnp.int32)
    hlo = lower_decode_hlo(cfg, placed, mesh, cache, tok)
    bad = integer_allgathers(hlo)
    n_ag = sum("all-gather" in ln for ln in hlo.splitlines())
    print(f"  decode HLO: {n_ag} all-gather lines, "
          f"{len(bad)} integer-payload all-gathers")
    summary = {
        "shards": shards, "wbits": args.wbits,
        "continuous": bool(args.continuous),
        "weight_bytes": int(qb),
        "weight_formats": leaf_format_histogram(params),
        "inventory": leaf_inventory(params),
        "streams_oracle": oracle, "streams_mesh": meshed,
        "identical": identical,
        "allgather_lines": int(n_ag),
        "integer_allgathers": bad,
        "prefill_logits_max_abs_diff": float(dl.max()),
    }
    if args.mesh_json:
        with open(args.mesh_json, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
        print(f"wrote {args.mesh_json}")
    obs_export(args)
    if not identical:
        raise SystemExit("mesh streams diverged from the oracle")
    if bad:
        raise SystemExit("weight payload bytes crossed devices:\n"
                         + "\n".join(bad))
    return summary


def main(argv=None):
    """Run the driver; returns ``(engine, finished requests)``, or with
    ``--mesh`` the comparison summary that ``--mesh-json`` writes."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--wbits", type=int, default=16, choices=[16, 8, 4, 3, 2])
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="tokens per prefill device call (0 = per-token)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (per-slot decode streams, "
                         "in-flight admission) instead of static rounds")
    ap.add_argument("--mesh", action="store_true",
                    help="tensor-parallel k-sharded serving over the host "
                         "mesh's model axis, differentially checked "
                         "bit-identical against the single-device oracle")
    ap.add_argument("--mesh-json", default=None, metavar="PATH",
                    help="with --mesh: dump streams + storage inventory + "
                         "collective audit (input to check_mesh.py)")
    add_obs_flags(ap)
    add_resilience_flags(ap)
    add_requant_flags(ap)
    args = ap.parse_args(argv)
    if args.requant:
        if not args.continuous:
            ap.error("--requant requires --continuous")
        if args.degrade:
            ap.error("--requant is incompatible with --degrade (both "
                     "hot-swap the served tree)")
        if args.mesh:
            ap.error("--requant does not support --mesh yet")
        if not obs_setup(args):
            obs.enable()   # the sense→act loop samples behind repro.obs
    else:
        obs_setup(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.mesh:
        return main_mesh(args, cfg)
    mesh = make_host_mesh()
    rng = np.random.default_rng(0)
    with use_mesh(mesh):
        params = serving_params(cfg)
        if not args.requant:
            params = _quantize_for_wbits(params, args.wbits)
        # the driver builds exactly ONE EngineConfig; every construction
        # path below (fresh, resumed, plan-driven) consumes it
        econfig = EngineConfig(
            n_slots=args.slots,
            max_len=args.prompt_len + args.max_new + 2,
            cache_dtype=SERVE_DTYPE,
            prefill_chunk=args.prefill_chunk or None,
            resilience=resilience_from_args(args, params),
            requant=requant_from_args(args))
        cls = ContinuousEngine if args.continuous else ServeEngine
        if args.resume:
            if not (args.continuous and args.snapshot_dir):
                ap.error("--resume needs --continuous and --snapshot-dir")
            eng = ContinuousEngine.resume(args.snapshot_dir, cfg, params,
                                          config=econfig)
            print(f"resumed from snapshot at tick {eng._tick} "
                  f"({eng.active_slots} slots live, "
                  f"{len(eng.queue)} queued)")
        elif args.requant:
            eng, _plan = _requant_engine(args, cfg, params, econfig)
        else:
            eng = cls(cfg, params, config=econfig)
        for i in range(args.requests):
            eng.submit(Request(
                rid=i,
                prompt=rng.integers(0, cfg.vocab,
                                    args.prompt_len).astype(np.int32),
                max_new_tokens=args.max_new))
        t0 = time.perf_counter()
        done = eng.run_until_done()
        if args.requant:
            # drifted second phase: repeated-token prompts collapse the
            # live Σ toward rank one; 2x the clean traffic so the drifted
            # samples dominate the streamed estimate and trip the
            # frobenius detectors
            for i in range(2 * args.requests):
                eng.submit(Request(
                    rid=args.requests + i,
                    prompt=np.full(args.prompt_len, 7, np.int32),
                    max_new_tokens=args.max_new))
            done += eng.run_until_done()
        dt = time.perf_counter() - t0
        total_tokens = sum(len(r.out_tokens) for r in done)
        sched = "continuous" if args.continuous else "static"
        print(f"served {len(done)} requests, {total_tokens} tokens "
              f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s, {sched})")
        if args.continuous:
            print(f"  steps={len(eng.step_stats)} "
                  f"prefill={eng.prefill_calls} calls/"
                  f"{eng.prefill_s*1e3:.0f}ms "
                  f"decode={eng.decode_calls} calls/"
                  f"{eng.decode_s*1e3:.0f}ms")
        else:
            for st in eng.round_stats:
                print(f"  round: b={st.batch} plen={st.prompt_len} "
                      f"prefill={st.prefill_calls} calls/"
                      f"{st.prefill_s*1e3:.0f}ms "
                      f"decode={st.decode_calls} calls/"
                      f"{st.decode_s*1e3:.0f}ms new={st.new_tokens}")
        ttfts = sorted(r.ttft_s for r in done if r.ttft_s is not None)
        if ttfts:
            p50 = ttfts[len(ttfts) // 2]
            print(f"  TTFT p50={p50*1e3:.0f}ms max={ttfts[-1]*1e3:.0f}ms")
        if eng.resilience is not None:
            for r in eng.dropped:
                print(f"  dropped rid={r.rid} ({r.drop_reason})")
            if eng.rung_history:
                print("  rungs: " + " -> ".join(
                    f"{name}@{tick}" for tick, name, _ in eng.rung_history))
        if args.requant:
            acts = eng.requant.actuations if eng.requant else []
            print(f"  requant actuations: {len(acts)}")
            for a in acts:
                moved = {n: (a['payload_before'][n], a['payload_after'][n])
                         for n in a['matrices']
                         if a['payload_before'][n] != a['payload_after'][n]}
                print(f"    tick={a['tick']} taps={','.join(a['taps'])} "
                      f"matrices={len(a['matrices'])} "
                      f"moved={moved or 'none'} "
                      f"replan={a['wall_s']*1e3:.0f}ms")
        for r in done[:4]:
            print(f"  rid={r.rid} out={r.out_tokens[:8]}")
        obs_export(args)
        return eng, done


if __name__ == "__main__":
    main()
