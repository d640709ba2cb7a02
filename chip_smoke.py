#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU at minicpm-2b full width.

    python3 chip_smoke.py              # one chip: phases a-c below
    python3 chip_smoke.py --chips 4    # four chips: k-sharded mesh serving

One process; JAX is imported once and no child process is started.  The
script exits non-zero, before any work and without a result line, when
JAX's first device is not a TPU.  No phase's exception is caught: a failed
check raises and the process exits non-zero.

One chip, minicpm-2b (40 layers, d_model 2304, 36 heads, d_ff 5760,
vocab 122753), random weights from seed 0:

  a. Kernel parity: the packed dequant-matmul (int4, int3, int2) at both
     MLP shapes (d_model→d_ff, d_ff→d_model) and m = 4 and 128 rows,
     against its XLA twin (kernels/dequant/ref.py) at HIGHEST precision.
  b. Serve through ``repro.launch.serve.main`` (``ContinuousEngine``, 4
     slots, 4 requests × 64 prompt tokens × 16 new tokens) at the bf16
     rung and at packed int4.  The bf16 rung's last-prompt-token logits
     are held against the full-sequence forward; the int4 decode program
     must contain the Mosaic kernel (``tpu_custom_call``).
  c. WaterSIC at 4 bits on layer 0's seven matrices, from Σ_X collected on
     2 calibration batches, beside RTN at the same bits.

``--chips 4`` runs only ``serve --mesh``: packed int4 with 4-way in-feature
sharding, compared with the single-device oracle on chip 0 and audited for
integer all-gathers in the decode program.

Every phase prints its compile seconds (lowering + XLA compile, summed from
``jax.monitoring``) and its run seconds (wall minus compile) on their own
lines, and the peak device memory so far.  The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import pack_codes_jnp, quantize_at_rate  # noqa: E402
from repro.core.rtn import distortion, rtn_absmax  # noqa: E402
from repro.kernels.dequant import dequant_matmul  # noqa: E402
from repro.kernels.dequant.dequant_matmul import PLANE_GROUPS  # noqa: E402
from repro.kernels.dequant.ref import dequant_matmul_packed_ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.transformer import forward_train  # noqa: E402
from repro.plan import collect_sigma_x  # noqa: E402
from repro.quant.calibrate import stats_for_matrix  # noqa: E402
from repro.quant.pipeline import matrix_tap_map  # noqa: E402

#: kernel vs XLA twin: max|out − ref| / max|ref|.  Codes are exact in bf16
#: and the kernel carries x·s as three exact bf16 terms, so it holds f32
#: accuracy (about 2e-7 against a float64 truth on a TPU v5e, int4 at
#: 1 and 8 rows); the bound also admits one bf16 rounding of x·s (2^-9
#: relative per product), as an f32 × f32 Mosaic dot at default precision
#: would make.
KERNEL_RTOL = 1e-2
#: bf16 serving vs the f32-activation reference forward: the mean (over
#: the prompts) relative L2 error of the engine's last-token logits may be
#: at most this multiple of the same error of the repo's own full forward
#: run in bf16.  A random-weight 40-layer model amplifies bf16 rounding to
#: 20–50% relative logit error for ANY bf16 computation of it, so the
#: served path is held to what bf16 itself allows (CPU rehearsals at depth
#: 40, widths 256–768: ratio 0.98–1.02).
PARITY_RATIO = 1.5
#: WaterSIC's entropy at the full rows vs the 4-bit target (the secant
#: search converges to 0.005 bits on a 10% row subsample; tests/
#: test_watersic.py holds the full-row rate to the same 0.05).
RATE_TOL_BITS = 0.05
WATERSIC_BITS = 4.0
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass(frozen=True)
class Smoke:
    """What the phases run at.  The program runs ``Smoke()``: minicpm-2b at
    full width with the Pallas kernels compiled for the chip.  The CPU
    rehearsal (tests/test_chip_smoke.py) runs the same phases with
    ``reduced=True``, kernels in interpret mode and no chip-only checks."""

    arch: str = "minicpm-2b"
    reduced: bool = False
    slots: int = 4
    prompt_len: int = 64
    max_new: int = 16
    kernel_rows: tuple = (4, 128)
    #: (batches, rows, tokens): 2 × 8 × 384 = 6144 samples ≥ d_ff, so
    #: every Σ_X of layer 0 has full rank
    calib: tuple = (2, 8, 384)
    interpret: bool = False
    chip_checks: bool = True

    def config(self):
        cfg = get_config(self.arch)
        return cfg.reduced() if self.reduced else cfg

    def serve_argv(self, *extra):
        argv = ["--arch", self.arch, "--continuous",
                "--slots", str(self.slots), "--requests", str(self.slots),
                "--prompt-len", str(self.prompt_len),
                "--max-new", str(self.max_new), *extra]
        return argv + (["--reduced"] if self.reduced else [])


class SmokeFailure(Exception):
    """A check of the smoke test did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Phase:
    """Wall clock, compile seconds and peak device memory of one phase."""

    def __init__(self, name: str, compile_clock: list):
        self.name = name
        self._clock = compile_clock

    def __enter__(self):
        print(f"[{self.name}]", flush=True)
        self._t0 = time.perf_counter()
        self._c0 = self._clock[0]
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self._t0
        comp = self._clock[0] - self._c0
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"  {self.name} compile_s {comp!r}", flush=True)
        print(f"  {self.name} run_s {wall - comp!r}", flush=True)
        print(f"  {self.name} peak_device_bytes "
              f"{peak if peak is not None else 'not reported'}", flush=True)
        return False


def _rel_l2(a, b):
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


def phase_kernels(sm: Smoke) -> None:
    """a. packed kernel vs its XLA twin at the model's two MLP shapes."""
    cfg = sm.config()
    key = jax.random.PRNGKey(1)
    for k, n in ((cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
        for nbits in (4, 3, 2):
            kz, ks, kt, kx = jax.random.split(
                jax.random.fold_in(key, 10 * k + nbits), 4)
            lo, hi = -(2 ** (nbits - 1)), 2 ** (nbits - 1) - 1
            z = jax.random.randint(kz, (n, k), lo, hi + 1)
            payload, er, _, _ = pack_codes_jnp(z, nbits=nbits)
            _check(er.shape[0] == 0, "in-range codes produced escapes")
            s = jax.random.uniform(ks, (k,), minval=0.01, maxval=0.21)
            t = jax.random.uniform(kt, (n,), minval=0.5, maxval=1.5)
            pad = PLANE_GROUPS[nbits] * payload.shape[-1] - k
            for m in sm.kernel_rows:
                x = jax.random.normal(jax.random.fold_in(kx, m), (m, k))
                out = dequant_matmul(x, payload, s, t,
                                     interpret=sm.interpret)
                with jax.default_matmul_precision("highest"):
                    ref = dequant_matmul_packed_ref(
                        jnp.pad(x, ((0, 0), (0, pad))), payload,
                        jnp.pad(s, (0, pad)), t, nbits=nbits)
                err = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
                print(f"  int{nbits} {k}->{n} m={m}: kernel vs XLA twin "
                      f"max rel err {err!r} (bound {KERNEL_RTOL})",
                      flush=True)
                _check(err <= KERNEL_RTOL,
                       f"int{nbits} {k}->{n} m={m}: err {err} > "
                       f"{KERNEL_RTOL}")


def phase_serve(sm: Smoke, wbits: int) -> None:
    """b. one rung through the serve driver; parity / kernel proof."""
    cfg = sm.config()
    eng, done = serve.main(sm.serve_argv("--wbits", str(wbits)))
    done = sorted(done, key=lambda r: r.rid)
    _check([r.rid for r in done] == list(range(sm.slots)),
           f"finished requests {[r.rid for r in done]}")
    _check(all(len(r.out_tokens) == sm.max_new for r in done),
           f"token counts {[len(r.out_tokens) for r in done]}")
    print(f"  wbits={wbits}: {len(done)} requests x {sm.max_new} tokens, "
          f"weight formats {eng.weight_formats}, weight bytes "
          f"{eng.weight_bytes}", flush=True)
    prompts = np.stack([r.prompt for r in done])
    logits = np.asarray(eng.prefill_logits(prompts), np.float32)
    _check(logits.shape == (sm.slots, cfg.vocab), f"logits {logits.shape}")
    _check(bool(np.isfinite(logits).all()), "non-finite served logits")
    if wbits == 16:
        fwd = jax.jit(lambda p, t: forward_train(cfg, p,
                                                 {"tokens": t})[:, -1, :])
        same = np.asarray(fwd(eng.params, prompts), np.float32)
        ref_params = dict(eng.params, embed={
            "w": eng.params["embed"]["w"].astype(jnp.float32)})
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(fwd(ref_params, prompts), np.float32)
        e_eng, e_fwd = _rel_l2(logits, ref), _rel_l2(same, ref)
        agree = float((logits.argmax(-1) == ref.argmax(-1)).mean())
        print(f"  bf16 parity vs f32-activation forward: engine rel L2 "
              f"{e_eng.tolist()!r}, bf16 full forward rel L2 "
              f"{e_fwd.tolist()!r}, ratio of means "
              f"{float(e_eng.mean() / e_fwd.mean())!r} (bound "
              f"{PARITY_RATIO}), top-1 agreement {agree!r}, engine vs "
              f"bf16 forward max abs {float(np.abs(logits - same).max())!r}",
              flush=True)
        _check(bool(np.isfinite(ref).all()), "non-finite reference logits")
        _check(e_eng.mean() <= PARITY_RATIO * e_fwd.mean(),
               "served bf16 logits further from the reference than bf16 "
               "allows")
    if wbits == 4 and sm.chip_checks:
        tok = jnp.zeros((sm.slots, 1), jnp.int32)
        text = eng._decode.lower(eng.params, eng.cache,
                                 tok).compile().as_text()
        n_calls = text.count("tpu_custom_call")
        print(f"  int4 decode program: {n_calls} tpu_custom_call sites",
              flush=True)
        _check(n_calls > 0, "int4 decode program has no Pallas kernel")


def phase_watersic(sm: Smoke) -> None:
    """c. WaterSIC at 4 bits on layer 0's matrices, beside RTN."""
    cfg = sm.config()
    full = serve.serving_params(cfg)
    # Σ_X of layer 0 depends only on the embedding and layer 0, so the
    # calibration forward runs the served model cut to its first layer
    one = {"embed": {"w": full["embed"]["w"].astype(jnp.float32)},
           "layers": jax.tree.map(lambda a: a[:1].astype(jnp.float32),
                                  full["layers"]),
           "ln_f": full["ln_f"]}
    del full
    cfg1 = dataclasses.replace(cfg, n_layers=1)
    n_batches, rows, toks = sm.calib
    rng = np.random.default_rng(2)
    calib = [rng.integers(0, cfg.vocab, (rows, toks)).astype(np.int32)
             for _ in range(n_batches)]
    t0 = time.perf_counter()
    acc = collect_sigma_x(cfg1, one, calib)
    print(f"  Sigma_X from {n_batches} x {rows} x {toks} tokens in "
          f"{time.perf_counter() - t0!r} s", flush=True)
    recs = [r for r in matrix_tap_map(cfg1, one) if r["layer"] == 0]
    _check(len(recs) == 7, f"{len(recs)} matrices in layer 0")
    for rec in recs:
        node = one["layers"]
        for k in rec["path"]:
            node = node[k]
        w = node["w"][0].T                           # (out, in)
        stats = stats_for_matrix(acc, 0, rec["tap"], use_drift=False)
        t0 = time.perf_counter()
        q = quantize_at_rate(w, stats, WATERSIC_BITS)
        w_hat = np.asarray(q.dequant(jnp.float32))
        dt = time.perf_counter() - t0
        # both distortions in f64 on the host, with one formula
        w, sigma = np.asarray(w), np.asarray(stats.sigma_x)
        d_ws = distortion(w, w_hat, sigma)
        d_rtn = distortion(w, rtn_absmax(w, int(WATERSIC_BITS))["w_hat"],
                           sigma)
        print(f"  {rec['name']} {tuple(w.shape)}: WaterSIC entropy "
              f"{q.entropy_bits!r} b (eff {q.rate_eff!r}), D {d_ws!r} | "
              f"RTN {int(WATERSIC_BITS)}b D {d_rtn!r} | "
              f"D ratio {d_ws / d_rtn!r} | {dt!r} s", flush=True)
        _check(abs(q.entropy_bits - WATERSIC_BITS) <= RATE_TOL_BITS,
               f"{rec['name']}: entropy {q.entropy_bits} misses "
               f"{WATERSIC_BITS} by more than {RATE_TOL_BITS}")
        _check(bool(np.isfinite(d_ws)), f"{rec['name']}: distortion {d_ws}")


def run(sm: Smoke, compile_clock: list) -> None:
    """Phases a–c on one device."""
    with Phase("a_kernel_parity", compile_clock):
        phase_kernels(sm)
    for wbits in (16, 4):
        with Phase(f"b_serve_wbits{wbits}", compile_clock):
            phase_serve(sm, wbits)
        gc.collect()
    with Phase("c_watersic", compile_clock):
        phase_watersic(sm)


def run_mesh(sm: Smoke, compile_clock: list) -> None:
    """``serve --mesh`` at packed int4 over every device, against the
    single-device oracle, with the integer all-gather audit."""
    with Phase("mesh_serve_int4", compile_clock):
        summary = serve.main(sm.serve_argv("--wbits", "4", "--mesh"))
        print(f"  shards {summary['shards']}, streams identical "
              f"{summary['identical']}, prefill logits max |oracle - mesh| "
              f"{summary['prefill_logits_max_abs_diff']!r}, all-gather "
              f"lines {summary['allgather_lines']}, integer all-gathers "
              f"{len(summary['integer_allgathers'])}", flush=True)
        _check(summary["identical"], "mesh streams diverged")
        _check(not summary["integer_allgathers"], "integer all-gathers")


def _compile_clock() -> list:
    """A one-slot accumulator of compile seconds fed by jax.monitoring."""
    clock = [0.0]

    def on_duration(event, secs, **_):
        if event in _COMPILE_EVENTS:
            clock[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return clock


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the k-sharded mesh serving path")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's first device is "
                 f"{dev.platform!r}")
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} devices")
    print(f"cache dir {enable_compile_cache()}", flush=True)
    print(f"device kind {dev.device_kind!r}, count {len(devices)}",
          flush=True)
    clock = _compile_clock()
    if args.chips == 4:
        run_mesh(Smoke(), clock)
    else:
        run(Smoke(), clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
