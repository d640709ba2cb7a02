"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (GSPMD partitions the whole step),
  * the program fits (memory_analysis),
  * and yields the roofline terms (cost_analysis + HLO collective bytes).

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k \
        --mesh single --out experiments/dryrun/
    python -m repro.launch.dryrun --all --mesh both   (sequential driver)

Writes one JSON per cell: experiments/dryrun/<arch>__<shape>__<mesh>.json
(existing files are skipped — the grid is resumable).

The dry-run always runs on 512 virtual CPU devices, also on a machine with
an accelerator: it pins ``JAX_PLATFORMS=cpu`` and appends the forced device
count to the caller's ``XLA_FLAGS``.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512")))
# ^ MUST precede any jax import: jax locks the device count on first init.

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, input_specs, list_archs
from repro.configs.base import ArchConfig, ShapeSpec
from repro.dist.sharding import (batch_spec, spec_for_axes, use_mesh)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import model_flops, report_from_artifacts
from repro.models import (decode_step, init_cache, init_params, loss_fn,
                          split_tree)
from repro.quant import quantize_params_tree
from repro.train import AdamWConfig, TrainState, adamw_init, make_train_step

__all__ = ["run_cell", "main"]


def _tree_specs(axes_tree, mesh):
    def to_spec(ax):
        return NamedSharding(mesh, spec_for_axes(ax))
    return jax.tree.map(to_spec, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def _dp_if_divisible(dim: int, mesh):
    """DP axes tuple if the batch dim divides evenly, else None (replicate —
    e.g. long_500k's global_batch=1)."""
    dp = batch_spec(mesh)
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    return dp if dim % n == 0 else None


def _batch_shardings(batch_sds, mesh):
    def shard(x):
        spec = [_dp_if_divisible(x.shape[0], mesh)] \
            + [None] * (len(x.shape) - 1)
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(shard, batch_sds)


def _abstract_params(cfg: ArchConfig, mesh, *, quantized: bool,
                     nbits: int = 8):
    px = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    params_sds, axes = split_tree(px)
    specs = _tree_specs(axes, mesh)
    if quantized:
        params_sds = jax.eval_shape(
            lambda p: quantize_params_tree(p, nbits=nbits), params_sds)
        # code dicts inherit the original weight's sharding; scales replicate
        specs = _qspec_tree(params_sds, specs, mesh)
    return params_sds, specs


def _qspec_tree(params_sds, specs, mesh):
    """Align a spec tree with a params tree whose weights became dicts."""
    def walk(p, s):
        if isinstance(p, dict) and "codes" in p:
            base = s if not isinstance(s, dict) else s.get("codes")
            spec = base.spec if hasattr(base, "spec") else P()
            sub = list(spec) + [None] * (p["codes"].ndim - len(spec))
            return {
                "codes": NamedSharding(mesh, P(*sub[: p["codes"].ndim])),
                "s": NamedSharding(mesh, P(*sub[: p["s"].ndim])),
                "t": NamedSharding(
                    mesh, P(*(list(sub[: p["codes"].ndim - 2])
                              + [sub[p["codes"].ndim - 1]]))
                    if p["t"].ndim > 1 else P(sub[p["codes"].ndim - 1])),
            }
        if isinstance(p, dict):
            return {k: walk(p[k], s[k]) for k in p}
        if isinstance(p, (list, tuple)):
            return type(p)(walk(a, b) for a, b in zip(p, s))
        return s
    return walk(params_sds, specs)


def _cache_specs(cfg: ArchConfig, cache_sds, mesh):
    """PartitionSpecs for decode caches: batch over DP (when divisible),
    kv-heads / state heads over model (when divisible)."""

    def mdl_if(dim):
        return "model" if dim % mesh.shape["model"] == 0 else None

    def by_shape(x):
        nd = len(x.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        dp = _dp_if_divisible(x.shape[1] if nd >= 2 else 1, mesh)
        if nd == 5:  # kv (L,B,n_kv/P,buf,P*hd) | rwkv wkv (L,B,H,dk,dv)
            return NamedSharding(mesh, P(None, dp, mdl_if(x.shape[2]), None,
                                         None))
        if nd == 4:  # rglru conv state (L,B,cw,lru)
            return NamedSharding(mesh, P(None, dp, None, mdl_if(x.shape[3])))
        if nd == 3:  # shift states (L,B,d) / rec h (L,B,lru)
            return NamedSharding(mesh, P(None, dp, mdl_if(x.shape[2])))
        return NamedSharding(mesh, P(*([None] * nd)))

    return jax.tree.map(by_shape, cache_sds)


def _auto_micro(cfg: ArchConfig, shape: ShapeSpec, mesh) -> int:
    env = os.environ.get("REPRO_N_MICRO")
    if env:
        return int(env)
    if cfg.microbatch:
        return cfg.microbatch
    dp = 1
    for a in batch_spec(mesh):
        dp *= mesh.shape[a]
    per_dev = max(shape.global_batch // dp, 1)
    n_micro = min(per_dev, 16)
    while shape.global_batch % n_micro:
        n_micro -= 1
    return max(n_micro, 1)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             wbits: int = 16, out_dir: str = "experiments/dryrun",
             force: bool = False, save_hlo: bool = False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_kind}" + \
        (f"__w{wbits}" if wbits != 16 else "")
    out_path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.subquadratic:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "skipped",
                  "reason": "full-attention arch: 500k KV decode out of "
                            "scope (DESIGN.md §5)"}
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    _HLO_DIR[0] = os.path.join(out_dir, tag + ".hlo.zz")
    t0 = time.time()
    try:
        with use_mesh(mesh):
            if shape.kind == "train":
                result = _lower_train(cfg, shape, mesh, mesh_kind)
            else:
                result = _lower_serve(cfg, shape, mesh, mesh_kind,
                                      prefill=(shape.kind == "prefill"),
                                      wbits=wbits)
    except Exception as e:  # noqa: BLE001 — recorded as cell failure
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "failed", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    result.update({"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                   "chips": chips, "wbits": wbits,
                   "elapsed_s": round(time.time() - t0, 1)})
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1, default=float)
    return result


_HLO_DIR = [None]  # set by run_cell so _collect can persist the HLO


def _collect(compiled, cfg, shape, mesh, mesh_kind, kind):
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    if _HLO_DIR[0]:
        import zlib
        with open(_HLO_DIR[0], "wb") as f:
            f.write(zlib.compress(hlo.encode(), 6))
    mem = compiled.memory_analysis()
    mem_info = {}
    peak = 0.0
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_info[attr] = int(v)
    peak = mem_info.get("argument_size_in_bytes", 0) + \
        mem_info.get("temp_size_in_bytes", 0)
    tokens = shape.global_batch * (shape.seq_len if kind != "decode" else 1)
    mf = model_flops(cfg.active_param_count(), tokens,
                     "train" if kind == "train" else "serve")
    rep = report_from_artifacts(
        arch=cfg.name, shape=shape.name, mesh=mesh_kind, chips=mesh.size,
        cost=dict(cost), hlo_text=hlo, model_flops_total=mf,
        mem_peak_bytes=peak)
    return {
        "status": "ok",
        "kind": kind,
        "memory_analysis": mem_info,
        "cost_analysis": {k: float(v) for k, v in dict(cost).items()
                          if isinstance(v, (int, float))},
        "roofline": rep.to_json(),
        "dominant": rep.dominant,
        "bound_time_s": rep.bound_time_s,
        "roofline_fraction": rep.roofline_fraction,
        "hlo_bytes": len(hlo),
        "n_collectives": {k: v for k, v in
                          rep.collective_breakdown.items()},
    }


def _lower_train(cfg, shape, mesh, mesh_kind):
    params_sds, pspecs = _abstract_params(cfg, mesh, quantized=False)
    opt_sds = jax.eval_shape(adamw_init, params_sds)
    opt_specs = type(opt_sds)(
        step=NamedSharding(mesh, P()), m=pspecs, v=pspecs)
    state_sds = TrainState(params=params_sds, opt=opt_sds, err=None)
    state_specs = TrainState(params=pspecs, opt=opt_specs, err=None)
    batch_sds = input_specs(cfg, shape)
    batch_specs = _batch_shardings(batch_sds, mesh)
    n_micro = _auto_micro(cfg, shape, mesh)
    step = make_train_step(cfg, AdamWConfig(schedule=cfg.lr_schedule),
                           n_micro=n_micro)
    jitted = jax.jit(step,
                     in_shardings=(state_specs, batch_specs),
                     out_shardings=(state_specs, None),
                     donate_argnums=(0,))
    lowered = jitted.lower(state_sds, batch_sds)
    compiled = lowered.compile()
    out = _collect(compiled, cfg, shape, mesh, mesh_kind, "train")
    out["n_micro"] = n_micro
    return out


def _lower_serve(cfg, shape, mesh, mesh_kind, *, prefill: bool, wbits: int):
    params_sds, pspecs = _abstract_params(cfg, mesh,
                                          quantized=(wbits in (8, 4)),
                                          nbits=max(wbits, 4) if wbits < 16 else 8)
    if prefill:
        from repro.models import prefill as prefill_fn
        batch_sds = input_specs(cfg, shape)
        batch_specs = _batch_shardings(batch_sds, mesh)
        fn = lambda p, b: prefill_fn(cfg, p, b, max_len=shape.seq_len)
        jitted = jax.jit(fn, in_shardings=(pspecs, batch_specs))
        lowered = jitted.lower(params_sds, batch_sds)
        compiled = lowered.compile()
        return _collect(compiled, cfg, shape, mesh, mesh_kind, "prefill")
    # decode: one new token against a seq_len-deep cache/state
    cache_sds = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len,
                           jnp.bfloat16))
    cache_specs = _cache_specs(cfg, cache_sds, mesh)
    tok_sds = input_specs(cfg, shape)
    tok_specs = _batch_shardings(tok_sds, mesh)
    fn = lambda p, c, t: decode_step(cfg, p, c, t["token"])
    jitted = jax.jit(fn, in_shardings=(pspecs, cache_specs, tok_specs),
                     out_shardings=(None, cache_specs),
                     donate_argnums=(1,))
    lowered = jitted.lower(params_sds, cache_sds, tok_sds)
    compiled = lowered.compile()
    return _collect(compiled, cfg, shape, mesh, mesh_kind, "decode")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--wbits", type=int, default=16,
                    choices=[16, 8, 4])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                r = run_cell(arch, shape, mesh_kind, wbits=args.wbits,
                             out_dir=args.out, force=args.force)
                status = r.get("status")
                dom = r.get("dominant", "-")
                print(f"{arch:24s} {shape:12s} {mesh_kind:6s} {status:8s} "
                      f"dominant={dom} t={r.get('elapsed_s', 0)}s",
                      flush=True)


if __name__ == "__main__":
    main()
