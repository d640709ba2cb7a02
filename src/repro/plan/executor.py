"""Parallel plan execution over host devices (DESIGN.md §10).

The sequential PTQ pipeline (quant/pipeline.py) quantizes layer l with
statistics of the *quantized-so-far* model, so layer l+1 cannot start
before layer l finishes — a serial chain by construction.  A `QuantPlan`
is built from fp-model statistics only, which makes every matrix's
quantization **independent**: the executor fans the per-matrix
`quantize_at_rate` calls out across a worker pool.  By default workers
share the backend's default device and one jit cache (XLA/BLAS release
the GIL, so the big factorizations overlap); ``devices="all"`` pins tasks
round-robin over every visible device (`jax.default_device`) — the
multi-device host mode, where each device runs its matrices truly
concurrently at the price of per-device compilation.

Determinism contract: a task's result depends only on (weights, stats,
target bits, damp, seed) — never on scheduling — so the parallel executor
is bit-identical to the sequential one (asserted in
tests/test_plan_executor.py).  Tasks are dispatched largest-first (LPT
scheduling) to balance the makespan.

Fault handling reuses `repro.dist` primitives: each task retries under a
:class:`~repro.dist.fault.RestartPolicy` (capped exponential backoff), an
optional :class:`~repro.dist.fault.Heartbeat` beats once per completed
task, and a :class:`~repro.dist.fault.StragglerMonitor` accumulates
per-device task times so chronically slow devices surface in the report.

Observability (DESIGN.md §11): with ``repro.obs`` enabled each task's
wall clock becomes a ``plan.task`` trace span (matrix/device/bits args)
plus a ``repro_plan_task_seconds`` histogram sample, the whole execution
a ``plan.execute`` span, and the fault machinery's outcomes surface as
``repro_plan_retries_total`` / ``repro_plan_stragglers_total`` counters
— the same numbers the :class:`ExecutorReport` carries, published live
instead of only at return.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.watersic import (CalibStats, QuantizedLinear,
                                 layer_distortion, quantize_at_rate)
from repro.dist.fault import Heartbeat, RestartPolicy, StragglerMonitor

from .artifact import QuantPlan

__all__ = ["ExecutorReport", "execute_plan", "quantize_model_with_plan"]


@dataclasses.dataclass
class ExecutorReport:
    """Scheduling/fault accounting for one plan execution."""

    n_workers: int
    wall_s: float
    task_s: Dict[str, float]            # matrix name → task wall clock
    device_of: Dict[str, str]           # matrix name → device label
    retries: int
    stragglers: List[str]               # flagged device labels

    @property
    def serial_s(self) -> float:
        """Sum of task times — the sequential-loop wall clock this
        execution's parallelism amortized."""
        return sum(self.task_s.values())


def _devices(n_workers: int, devices) -> Optional[List[Any]]:
    """None (default) = no pinning: all tasks share the backend default
    device and one jit cache — the right call for a single big host.
    "all" = round-robin over every visible device (multi-device hosts:
    each device compiles its own executables and runs truly concurrently).
    An explicit list pins to those devices."""
    if devices is None:
        return None
    import jax
    devs = list(jax.devices()) if devices == "all" else list(devices)
    return devs[:max(1, n_workers)] if len(devs) >= n_workers else devs


def execute_plan(plan: QuantPlan,
                 weights: Dict[str, Any],
                 stats: Dict[str, CalibStats], *,
                 damp: float = 0.05,
                 seed: int = 0,
                 n_workers: int = 1,
                 devices=None,
                 policy: Optional[RestartPolicy] = None,
                 heartbeat: Optional[Heartbeat] = None,
                 compute_distortion: bool = True,
                 quantize_kwargs: Optional[Dict[str, Any]] = None,
                 subset: Optional[Sequence[str]] = None,
                 ) -> Tuple[Dict[str, QuantizedLinear], ExecutorReport]:
    """Quantize every plan entry at its snapped target, in parallel.

    ``weights[name]`` is the (out, in) algorithm-layout matrix and
    ``stats[name]`` its :class:`CalibStats`; both must cover every entry.
    Fills ``entry.achieved_bits`` (entropy) and, when
    ``compute_distortion``, ``entry.realized_distortion`` in place.
    Returns ``(qlinears, report)``.

    ``subset`` restricts execution to those entry names (incremental
    mode, the requant actuator's path — DESIGN.md §15): only the named
    matrices are quantized, ``weights``/``stats`` need cover only them,
    and only their entries get achieved/realized fields filled; the
    returned ``qlinears`` contains exactly the executed names.
    """
    import jax
    if subset is None:
        entries = list(plan.entries)
    else:
        sub = set(subset)
        unknown = sorted(n for n in sub if n not in plan)
        if unknown:
            raise KeyError(f"subset names not in plan: {unknown[:5]}"
                           f"{'...' if len(unknown) > 5 else ''}")
        entries = [e for e in plan.entries if e.name in sub]
    missing = [e.name for e in entries if e.name not in weights
               or e.name not in stats]
    if missing:
        raise KeyError(f"plan entries without weights/stats: {missing[:5]}"
                       f"{'...' if len(missing) > 5 else ''}")
    tmpl = policy or RestartPolicy(max_restarts=2, backoff_base_s=0.01,
                                   backoff_max_s=0.1)
    devs = _devices(n_workers, devices)
    monitor = StragglerMonitor(threshold=3.0)
    retries = 0
    retry_lock = threading.Lock()
    results: Dict[str, QuantizedLinear] = {}

    # LPT: largest matrices first so the pool's makespan stays balanced
    order = sorted(entries, key=lambda e: -e.n_params)

    def run_one(task_idx: int, entry) -> Tuple[str, QuantizedLinear, float,
                                               str]:
        nonlocal retries
        dev = devs[task_idx % len(devs)] if devs else None
        pol = dataclasses.replace(tmpl)
        dev_label = str(dev) if dev is not None else "default"
        with obs.span("plan.task", matrix=entry.name, device=dev_label,
                      bits=float(entry.execution_bits)) as sp:
            t0 = time.perf_counter()
            while True:
                try:
                    if dev is None:
                        q = quantize_at_rate(
                            weights[entry.name], stats[entry.name],
                            float(entry.execution_bits), damp=damp,
                            seed=seed, **(quantize_kwargs or {}))
                    else:
                        with jax.default_device(dev):
                            q = quantize_at_rate(
                                weights[entry.name], stats[entry.name],
                                float(entry.execution_bits), damp=damp,
                                seed=seed, **(quantize_kwargs or {}))
                    break
                except Exception:
                    delay = pol.next_delay()
                    if delay is None:
                        raise
                    with retry_lock:
                        retries += 1
                    obs.counter("repro_plan_retries_total").inc()
                    time.sleep(delay)
            t1 = time.perf_counter()
            sp.stamp(t0, t1)
        if obs.enabled():
            obs.counter("repro_plan_tasks_total").inc()
            obs.histogram("repro_plan_task_seconds").observe(t1 - t0)
        return (entry.name, q, t1 - t0, dev_label)

    task_s: Dict[str, float] = {}
    device_of: Dict[str, str] = {}
    with obs.span("plan.execute", n_workers=n_workers,
                  tasks=len(order)) as sp:
        t_start = time.perf_counter()
        pool = ThreadPoolExecutor(max_workers=n_workers) if n_workers > 1 \
            else None
        try:
            done = (pool.map(run_one, range(len(order)), order) if pool
                    else (run_one(i, e) for i, e in enumerate(order)))
            # consume lazily: the heartbeat/straggler feed advances as
            # tasks complete (in submission order), not only after the
            # whole pool drains — an external watchdog sees live progress
            # mid-execution
            for k, (name, q, dt, dev) in enumerate(done):
                results[name] = q
                task_s[name] = dt
                device_of[name] = dev
                monitor.observe(dev, dt)
                if heartbeat is not None:
                    heartbeat.beat(k + 1)
        finally:
            if pool is not None:
                pool.shutdown(wait=True)
        t_done = time.perf_counter()
        sp.stamp(t_start, t_done)
        sp.set(retries=retries)
    wall = t_done - t_start
    stragglers = monitor.stragglers()
    if obs.enabled() and stragglers:
        obs.counter("repro_plan_stragglers_total").inc(len(stragglers))

    for e in entries:
        q = results[e.name]
        e.achieved_bits = float(q.entropy_bits)
        if compute_distortion:
            e.realized_distortion = float(layer_distortion(
                np.asarray(weights[e.name]), q,
                np.asarray(stats[e.name].sigma_x)))
    report = ExecutorReport(n_workers=n_workers, wall_s=wall, task_s=task_s,
                            device_of=device_of, retries=retries,
                            stragglers=stragglers)
    return results, report


# ---------------------------------------------------------------------------
# Model-level wrapper: calibrate → execute → write dequantized weights back
# ---------------------------------------------------------------------------


def plan_inputs_for_model(cfg, params, calib_batches
                          ) -> Tuple[Dict[str, Any], Dict[str, CalibStats]]:
    """(weights, stats) dicts covering every plan entry of a dense/moe
    model, from ONE fp calibration pass (no drift statistics — plan
    execution is the independent-layer path; DESIGN.md §10)."""
    import jax.numpy as jnp

    from repro.quant import pipeline as _pl
    from .sensitivity import collect_sigma_x
    acc = collect_sigma_x(cfg, params, calib_batches)
    mats = _pl._mats_for(cfg, params)
    L = _pl._layer_count(params)
    weights: Dict[str, Any] = {}
    stats: Dict[str, CalibStats] = {}
    for l in range(L):
        for path, tap, _ in mats:
            name = f"L{l}/{'/'.join(path)}"
            weights[name] = jnp.asarray(_pl._get_w(params, l, path)).T
            stats[name] = CalibStats(sigma_x=jnp.asarray(
                acc.get(f"L{l}/{tap}/xx"), jnp.float32))
        if cfg.n_experts:
            for key in _pl._expert_keys(params):
                tap = "hid" if key == "w_out" else "in"
                for e in range(cfg.n_experts):
                    name = f"L{l}/moe/{key}/e{e}"
                    weights[name] = jnp.asarray(
                        params["layers"]["moe"][key][l, e]).T
                    stats[name] = CalibStats(sigma_x=jnp.asarray(
                        acc.get(f"L{l}/e{e}/{tap}/xx"), jnp.float32))
    return weights, stats


def quantize_model_with_plan(cfg, params, calib_batches, plan: QuantPlan, *,
                             damp: float = 0.05, seed: int = 0,
                             n_workers: int = 1, devices=None,
                             compute_distortion: bool = False,
                             heartbeat: Optional[Heartbeat] = None):
    """Execute a plan against a model: parallel per-matrix quantization,
    dequantized weights written back into a param copy.

    Returns ``(qparams, qlinears, plan, report)`` — the plan comes back
    with achieved bits filled in, mirroring quantize_model's budget
    return.  The drift/residual corrections of the sequential pipeline do
    not apply here (they would chain layers); `quantize_model(plan=...)`
    keeps them and stays sequential.
    """
    import copy

    import jax
    import jax.numpy as jnp

    from repro.quant import pipeline as _pl
    weights, stats = plan_inputs_for_model(cfg, params, calib_batches)
    # upfront coverage check (mirrors quantize_model's): a plan built for
    # another arch must fail BEFORE minutes of quantization, not at the
    # write-back KeyError after it
    missing = sorted(set(weights) - set(plan.names()))
    if missing:
        raise KeyError(f"plan is missing entries for {missing[:5]}"
                       f"{'...' if len(missing) > 5 else ''} — built for "
                       "a different model?")
    qlinears, report = execute_plan(
        plan, weights, stats, damp=damp, seed=seed, n_workers=n_workers,
        devices=devices, heartbeat=heartbeat,
        compute_distortion=compute_distortion)
    qparams = jax.tree.map(lambda x: x, params)
    qparams = copy.deepcopy(jax.device_get(jax.tree.map(jnp.asarray,
                                                        qparams)))
    qparams = jax.tree.map(jnp.asarray, qparams)
    mats = _pl._mats_for(cfg, params)
    L = _pl._layer_count(params)
    rows = []
    for l in range(L):
        for path, _, _ in mats:
            name = f"L{l}/{'/'.join(path)}"
            q = qlinears[name]
            _pl._set_w(qparams, l, path, q.dequant().T)
            rows.append({"layer": l, "matrix": "/".join(path),
                         "rate": q.rate_eff, "entropy": q.entropy_bits,
                         "dead": int(q.dead_mask.sum())})
        if cfg.n_experts:
            for key in _pl._expert_keys(params):
                for e in range(cfg.n_experts):
                    name = f"L{l}/moe/{key}/e{e}"
                    q = qlinears[name]
                    leaf = qparams["layers"]["moe"][key]
                    qparams["layers"]["moe"][key] = leaf.at[l, e].set(
                        q.dequant().T.astype(leaf.dtype))
                    rows.append({"layer": l, "matrix": f"moe/{key}/e{e}",
                                 "rate": q.rate_eff,
                                 "entropy": q.entropy_bits,
                                 "dead": int(q.dead_mask.sum())})
    return qparams, qlinears, plan, report
