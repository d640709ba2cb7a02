"""Host milliseconds per decode step in which the (rows, vocab) logits
travel to the host and the argmax runs there: the mean length of the
program's ``serve.decode.sync`` spans in the traced window, one in each
``serve.decode`` (profiler trace, program spans)."""
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    r = spans.for_run(run, ROOT)
    n = r["span_count"].get("serve.decode.sync") if r else None
    return 1e3 * r["span_s"]["serve.decode.sync"] / n if n else None
