"""Compile the serving kernels and programs for a described TPU v5e at the
widths of the served configurations (minicpm-2b, minitron-8b).

Nothing runs here.  Each kernel test lowers a public dequant-matmul op's
jitted body for one chip of a ``v5e:2x2`` topology (which the installed TPU
compiler describes without hardware) and asserts the Mosaic kernel
(``tpu_custom_call``) is in the compiled program: the chip's compiler
accepts the kernel's tiling and VMEM use at real widths, and no XLA
reference twin took its place.  The ops pick the Pallas branch from
``jax.default_backend()``, which still reports the CPU here, so each test
steers that one call to ``"tpu"`` for the duration of its compile.  The
packed kernel's Mosaic module is read as it is serialized, to check the
operand types of its MXU contractions.  The serving test compiles the
engine's decode step and admission chunk at minitron-8b's full size and
reads their memory from the compiler; another holds both configurations'
serving programs to updating the donated KV cache in place.
"""
import re
import sys
from pathlib import Path

import jax
import jax._src.tpu_custom_call as tpu_custom_call
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dequant import ops

#: minicpm-2b MLP widths: d_model → d_ff (w_gate/w_up) and d_ff → d_model
MLP_SHAPES = [(2304, 5760), (5760, 2304)]
#: the same and the attention projections (d_model → d_model)
PACKED_SHAPES = MLP_SHAPES + [(2304, 2304)]
DECODE_ROWS = 4
#: minitron-8b widths: wq, wk and wv, wo, w_in, w_out
MINITRON_SHAPES = [(4096, 6144), (4096, 1024), (6144, 4096), (4096, 16384),
                   (16384, 4096)]
#: rows the engine sends the packed kernel: one prompt token, a decode step
#: of minicpm-2b's 8 slots
PACKED_ROWS = [1, 8]
#: (nbits, k, n, m): every rung at minicpm-2b's widths and rows, and int4
#: (the rung the benchmark serves) at the 16 rows of minitron-8b's decode
#: step, at minicpm-2b's widths and at minitron-8b's
PACKED_CASES = (
    [(nbits, k, n, m) for nbits in (4, 3, 2) for k, n in PACKED_SHAPES
     for m in PACKED_ROWS]
    + [(4, k, n, 16) for k, n in PACKED_SHAPES]
    + [(4, k, n, m) for k, n in MINITRON_SHAPES for m in PACKED_ROWS + [16]])
#: planar payload shape (n, k) → uint8 payload, by nbits (core/packing)
PAYLOAD = {4: lambda n, k: (n, -(-k // 2)),
           3: lambda n, k: (n, 3, -(-k // 8)),
           2: lambda n, k: (n, 1, -(-k // 4))}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler or libtpu held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a chip compile written to the persistent cache cannot be read
        # back without the chip; keep the cache out of these compiles
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()
            # drop traces made with the steered backend before CPU tests
            # in this process trace the same functions again
            jax.clear_caches()


def _compiled_text(monkeypatch, jitted, args, **static):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return jitted.lower(*args, **static).compile().as_text()


def _mosaic_modules(monkeypatch):
    """Record the text of every Mosaic module serialized from now on."""
    texts = []
    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def record(module, **kw):
        texts.append(str(module))
        return serialize(module, **kw)
    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm",
                        record)
    return texts


@pytest.mark.parametrize("nbits,k,n,m", PACKED_CASES)
def test_packed_kernel_compiles_for_v5e(one_chip, monkeypatch, nbits, k, n,
                                        m):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds((m, k), jnp.bfloat16),
            sds(PAYLOAD[nbits](n, k), jnp.uint8),
            sds((k,), jnp.float32), sds((n,), jnp.float32))
    jax.clear_caches()          # lower afresh, so the module is serialized
    modules = _mosaic_modules(monkeypatch)
    text = _compiled_text(monkeypatch, ops._dequant_matmul_packed, args,
                          nbits=nbits)
    assert "tpu_custom_call" in text
    # the kernel's name is its instruction's, which a device trace shows
    assert f"%dequant_matmul_packed_int{nbits}" in text
    # the MXU contracts exact bf16 codes against bf16 terms of x·s, never
    # f32 × f32 (emulated in several bf16 passes)
    matmuls = [line for mod in modules for line in mod.splitlines()
               if "tpu.matmul" in line]
    assert matmuls
    for line in matmuls:
        lhs, rhs = re.search(r":\s*vector<([^>]+)>,\s*vector<([^>]+)>",
                             line).groups()
        assert lhs.endswith("bf16") and rhs.endswith("bf16"), line


@pytest.mark.parametrize("k,n", MLP_SHAPES)
def test_int8_kernel_compiles_for_v5e(one_chip, monkeypatch, k, n):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sds((DECODE_ROWS, k), jnp.bfloat16), sds((n, k), jnp.int8),
            sds((k,), jnp.float32), sds((n,), jnp.float32))
    text = _compiled_text(monkeypatch, ops._dequant_matmul_int8, args)
    assert "tpu_custom_call" in text
    assert "%dequant_matmul_int8" in text


def _serving_setup(one_chip, name):
    """(spec, the engine's step and chunk, the parameters, a cache maker,
    a token-array maker) for configuration ``name`` of the benchmark, every
    argument placed on the described chip."""
    bench = str(Path(__file__).resolve().parents[1] / "bench")
    if bench not in sys.path:
        sys.path.append(bench)
    import harness
    import weights
    from spec import load_spec

    from repro.models.transformer import init_cache
    from repro.serve.engine import _serving_programs
    spec = load_spec(name)
    cfg = harness.program_config(spec)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda k: weights._program_tree(spec, k), weights.seed_key(0)))

    def cache(batch, per_slot):
        return on_chip(jax.eval_shape(lambda: init_cache(
            cfg, batch, spec.max_len, jnp.bfloat16, per_slot=per_slot)))
    return (spec, _serving_programs(cfg), params, cache,
            lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                               sharding=one_chip))


def test_minitron_serving_programs_fit_v5e(one_chip, monkeypatch):
    """minitron-8b.int4 as the benchmark serves it (bench/configs), on one
    v5e: the decode step over 16 slots x 640 positions, and an admission
    burst of 16 rows (``decode_chunk`` at (16, 16)) beside the engine's own
    cache, fit 16 GiB by the compiler's own count."""
    spec, (step, chunk), params, cache, toks = _serving_setup(
        one_chip, "minitron-8b.int4")
    slots = spec.slots
    engine_cache = cache(slots, True)
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(engine_cache))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    budget = 16 * 2**30
    for compiled, resident in (
            (step.lower(params, engine_cache, toks((slots, 1))).compile(),
             0),
            (chunk.lower(params, cache(slots, False),
                         toks((slots, spec.prefill_chunk))).compile(),
             cache_bytes)):
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes
                 + resident)
        assert total < budget, (total / 2**30, mem)


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = (\(.*?\)|\w+\[[\d,]*\]"
                          r"(?:\{[^}]*\})?) ([\w\-]+)\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")
#: what may produce the whole stacked cache: an in-place update of it, or
#: an op that only passes the buffer on
_IN_PLACE = ("dynamic-update-slice", "scatter")
_PASS_ON = ("parameter", "get-tuple-element", "tuple", "while", "bitcast")


def _top_level_instructions(text):
    """(opcode, its fused root's opcode or None, output dims) of every
    instruction in a computation that no fusion or reduction calls: the
    entry and the loop bodies, the ops a device runs one by one."""
    comps, name = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.strip() == "}":
            name = None
        elif name is not None and (m := _INSTRUCTION.match(line)):
            called = re.search(r"calls=%([\w.\-]+)", line)
            comps[name].append((m.group(1), m.group(4),
                                called and called.group(1),
                                [tuple(int(d) for d in dims.split(",") if d)
                                 for dims in _ARRAY.findall(m.group(3))],
                                " fusion(" in line or "to_apply=" in line))
    inner = {called for body in comps.values()
             for _, _, called, _, fusion in body if called and fusion}
    inner |= set(re.findall(r"to_apply=%([\w.\-]+)", text))
    roots = {n: op for n, body in comps.items()
             for root, op, _, _, _ in body if root}
    return [(op, roots.get(called), dims)
            for n, body in comps.items() if n not in inner
            for _, op, called, shapes, _ in body for dims in shapes]


@pytest.mark.parametrize("name", ["minicpm-2b.int4", "minitron-8b.int4"])
@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_serving_programs_update_cache_in_place(one_chip, monkeypatch, name,
                                               program):
    """The engine's decode step over its slots, and a one-row prefill
    chunk, at each benchmark configuration's shapes on one v5e: the
    compiled program aliases the donated cache to its output, no top-level
    instruction materialises one layer's K or V (in any axis order), and
    every instruction that produces the whole stacked cache updates it in
    place."""
    spec, (step, chunk), params, cache, toks = _serving_setup(one_chip,
                                                             name)
    rows = spec.slots if program == "decode_step" else 1
    kv = cache(rows, program == "decode_step")
    fn, tok = {"decode_step": (step, toks((rows, 1))),
               "prefill_chunk": (chunk, toks((rows, spec.prefill_chunk)))}[
                   program]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = fn.lower(params, kv, tok).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    stack = kv.kv.k.shape                      # (L, B, n_kv, hd, buf)
    layer = sorted(d for d in stack[1:] if d != 1)
    for op, root, dims in _top_level_instructions(compiled.as_text()):
        assert sorted(d for d in dims if d != 1) != layer, (op, root, dims)
        if dims == stack:
            assert op in _PASS_ON + _IN_PLACE or (
                op == "fusion" and root in _IN_PLACE), (op, root, dims)
