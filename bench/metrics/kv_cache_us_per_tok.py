"""Device microseconds of the KV cache's own work (ops under the
program's ``kv_cache`` scope: the step's cache writes, the admission
sub-cache, grafts and resets; and the copies XLA inserts whose output has
a cache leaf's shape) per real token of the traced dispatches."""
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    r = spans.for_run(run, ROOT)
    tokens = spans.traced_tokens(run)
    if not r or not tokens or not any(
            s in r["scopes"] for s in spans.SCOPES if s != "kv_cache"):
        return None
    return 1e6 * r["scopes"].get("kv_cache", 0.0) / tokens
