"""Model configuration as the benchmark reads it: sizes from a file under
``bench/configs/``, and the list of weight matrices they imply.

Imports nothing of the program: the reference, the weight generator and
the roofline functions all take their shapes from here.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Matrix:
    """One stacked weight matrix of the decoder layers."""

    group: str        # "attn" | "mlp"
    name: str         # program leaf name: wq, wk, wv, wo, w_gate, w_up, ...
    n_in: int
    n_out: int


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    activation: str          # "silu" (gated) | "relu2" (ungated)
    gated_mlp: bool
    rope_theta: float
    norm_eps: float
    slots: int
    max_len: int             # cache positions per slot
    prefill_chunk: int
    max_gap_sigma: float     # limit of the correctness comparison
    raw: dict = dataclasses.field(compare=False, repr=False)

    @property
    def padded_vocab(self) -> int:
        return ((self.vocab + 255) // 256) * 256

    def matrices(self) -> List[Matrix]:
        d, hq, hk = self.d_model, self.n_heads * self.head_dim, \
            self.n_kv * self.head_dim
        out = [Matrix("attn", "wq", d, hq), Matrix("attn", "wk", d, hk),
               Matrix("attn", "wv", d, hk), Matrix("attn", "wo", hq, d)]
        if self.gated_mlp:
            out += [Matrix("mlp", "w_gate", d, self.d_ff),
                    Matrix("mlp", "w_up", d, self.d_ff)]
        else:
            out += [Matrix("mlp", "w_in", d, self.d_ff)]
        out += [Matrix("mlp", "w_out", self.d_ff, d)]
        return out

    def layer_params(self) -> int:
        return sum(m.n_in * m.n_out for m in self.matrices())


def spec_from_dict(raw: dict) -> ModelSpec:
    serving, check = raw["serving"], raw["check"]
    return ModelSpec(
        name=raw["name"],
        layers=raw["num_hidden_layers"], d_model=raw["hidden_size"],
        n_heads=raw["num_attention_heads"], n_kv=raw["num_key_value_heads"],
        head_dim=raw["head_dim"], d_ff=raw["intermediate_size"],
        vocab=raw["vocab_size"], activation=raw["hidden_act"],
        gated_mlp=raw["gated_mlp"], rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        slots=serving["slots"],
        max_len=serving["max_len"],
        prefill_chunk=serving["prefill_chunk"],
        max_gap_sigma=float(check["max_gap_sigma"]), raw=raw)


def load_json(kind: str, name: str, root: Path = BENCH) -> dict:
    """``bench/<kind>/<name>.json``; a missing file is an error."""
    path = root / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def load_spec(name: str, root: Path = BENCH) -> ModelSpec:
    return spec_from_dict(load_json("configs", name, root))

