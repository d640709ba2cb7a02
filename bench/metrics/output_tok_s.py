"""Output tokens emitted by the steps begun in the window, over the time
from the window's start to the end of the last of those steps."""


def read(run):
    w = run.window
    if w.span_s <= 0:
        return None
    return sum(s[4] for s in w.steps) / w.span_s
