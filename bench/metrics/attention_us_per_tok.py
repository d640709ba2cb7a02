"""Device microseconds of decode attention (scores, softmax, the value
sum: ops under the program's ``attention`` scope) per real token of the
traced dispatches (rows × tokens, as ``packed_matmul_roofline`` counts
them)."""
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    r = spans.for_run(run, ROOT)
    tokens = spans.traced_tokens(run)
    if not r or not tokens or "attention" not in r["scopes"]:
        return None
    return 1e6 * r["scopes"]["attention"] / tokens
