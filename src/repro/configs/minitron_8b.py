"""minitron-8b [dense]: 32L d_model=4096, 48 query heads x 128 over 8 KV
heads (GQA group 6; the query width 6144 is not d_model), ungated relu² MLP
d_ff=16384, vocab=256000 — width-pruned Nemotron-4 15B.
[arXiv:2407.14679; hf nvidia/Minitron-8B-Base config.json]

Departures from the published block, which the program does not model:
  * RMSNorm in place of Nemotron's LayerNorm1p (layer norm with bias, the
    scale stored as scale - 1);
  * full rotary embeddings in place of ``partial_rotary_factor`` 0.5;
  * a tied LM head in place of the published untied one (a decode step
    reads the same bytes; the chip holds 2.1 GB less).
"""
from .base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="minitron-8b", family="dense",
    n_layers=32, d_model=4096, n_heads=48, n_kv=8, head_dim=128,
    d_ff=16384, vocab=256000,
    activation="relu2", gated_mlp=False,
    source="arXiv:2407.14679; hf",
))
