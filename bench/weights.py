"""Seeded weights: int4 codes and their scales, drawn on the device.

``draw_*`` are the benchmark's own and import nothing of the program: the
reference calls them to rebuild, layer by layer, the very weights the
program serves.  ``program_params`` hands the same draws to the program's
packer (``pack_codes_jnp``) and lays them out as the program's serving
tree, in one jitted call: no bf16 master tree is ever built.

Codes are a random nibble minus 8, with -8 made 0: each of -7..7 has
probability 1/16 and 0 has 2/16, all inside the int4 payload's [-8, 7], so
the packer makes no escapes.  A column scale ``s`` and a row scale ``t``, each
uniform on [0.5, 1.5] times a base, give the dequantized weight
``s[i] * code[o, i] * t[o]`` the program's init variance 1/fan_in.  The
embedding (tied LM head) stays bf16, N(0, 0.02^2), and norm scales are ones,
as the program's ``init_params`` makes them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from spec import Matrix, ModelSpec

#: E[code^2] of the codes above, and E[u^2] of u ~ U(0.5, 1.5)
_CODE_SQ = 2 * sum(c * c for c in range(1, 8)) / 16
_UNIT_SQ = 13.0 / 12.0
EMBED_STD = 0.02


def seed_key(seed: int):
    """A threefry key from any whole-number seed (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def draw_matrix(key, spec: ModelSpec, index: int, layer):
    """Layer ``layer``'s codes (out, in) int8, column scale (in,) and row
    scale (out,) f32 for matrix ``index`` of ``spec.matrices()``."""
    m: Matrix = spec.matrices()[index]
    k = jax.random.fold_in(jax.random.fold_in(key, 1000 + index), layer)
    kz, ks, kt = jax.random.split(k, 3)
    nib = (jax.random.bits(kz, (m.n_out, m.n_in), jnp.uint8) & 0xF)
    codes = nib.astype(jnp.int8) - 8
    codes = jnp.where(codes == -8, jnp.int8(0), codes)
    base = 1.0 / math.sqrt(m.n_in * _CODE_SQ * _UNIT_SQ * _UNIT_SQ)
    s = jax.random.uniform(ks, (m.n_in,), jnp.float32, 0.5, 1.5) * base
    t = jax.random.uniform(kt, (m.n_out,), jnp.float32, 0.5, 1.5)
    return codes, s, t


def draw_embed(key, spec: ModelSpec):
    """(padded_vocab, d_model) bf16 embedding, tied to the LM head."""
    k = jax.random.fold_in(key, 1)
    w = jax.random.normal(k, (spec.padded_vocab, spec.d_model), jnp.float32)
    return (w * EMBED_STD).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(0,))
def _program_tree(spec: ModelSpec, key):
    from repro.core.packing import pack_codes_jnp

    def leaf(index):
        def one(layer):
            codes, s, t = draw_matrix(key, spec, index, layer)
            payload, er, ec, ev = pack_codes_jnp(codes, nbits=4,
                                                 escape_capacity=0)
            return {"codes": payload, "s": s, "t": t,
                    "esc_row": er, "esc_col": ec, "esc_dval": ev}
        return {"w": jax.lax.map(one, jnp.arange(spec.layers))}

    mats = spec.matrices()
    groups = {"attn": {}, "mlp": {}}
    for i, m in enumerate(mats):
        groups[m.group][m.name] = leaf(i)
    ones = jnp.ones((spec.layers, spec.d_model), jnp.float32)
    return {"embed": {"w": draw_embed(key, spec)},
            "layers": {"ln_attn": {"scale": ones}, "attn": groups["attn"],
                       "ln_mlp": {"scale": ones}, "mlp": groups["mlp"]},
            "ln_f": {"scale": jnp.ones((spec.d_model,), jnp.float32)}}


def program_params(spec: ModelSpec, seed: int):
    """The program's packed-int4 serving tree for ``spec`` from ``seed``."""
    return _program_tree(spec, seed_key(seed))
