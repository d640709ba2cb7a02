"""Prefill milliseconds per prompt token: the engine's own ``prefill_s``
(device time to logits-ready of every admission) over the prompt tokens
admitted in the window."""


def read(run):
    w = run.window
    if not w.prompt_tokens:
        return None
    return 1e3 * w.engine["prefill_s"] / w.prompt_tokens
