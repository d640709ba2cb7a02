"""The comparison that decides ``correct``: sound runs pass, and the
control fails.  The faults a serving cell can have are in test_run.py.

The control (the reference in float8) is held to the same limit in
``test_control_fails``.  These run at a size a CPU test can hold; the
readings that set the chip limit are in PERF.md.
"""
import numpy as np
import pytest

import check
from conftest import TINY_CHAT, config_limits, serve_tiny

SEED = 2**35 + 77


def test_sound_run_is_correct(tiny_spec):
    w, done = serve_tiny(tiny_spec, TINY_CHAT, SEED)
    assert w.unanswered == 0 and w.compiles == 0 and len(done) >= 8
    v = check.judge(tiny_spec, SEED, done, TINY_CHAT["check_requests"])
    assert v["correct"], v
    assert v["requests"] == 4 and v["tokens"] > 20


def test_sample_holds_the_longest_and_follows_the_seed(tiny_spec):
    _, done = serve_tiny(tiny_spec, TINY_CHAT, SEED)
    a = check.sample(done, SEED, 4)
    longest = max(len(r.prompt) + len(r.out_tokens) for r in done)
    assert len(a[0].prompt) + len(a[0].out_tokens) == longest
    assert [r.rid for r in a] == [r.rid for r in check.sample(done, SEED, 4)]


def test_sample_spreads_over_the_slots():
    """With n above half the slots, a fault in half of the batch's rows
    always has a request in the sample."""
    from types import SimpleNamespace
    reqs = [SimpleNamespace(rid=i, prompt=[0] * 16, out_tokens=[0] * (i + 1))
            for i in range(40)]
    slot_of = {i: i % 16 for i in range(40)}
    for seed in (1, 2**40 + 3):
        picks = check.sample(reqs, seed, 9, slot_of)
        assert picks[0].rid == 39 and len({r.rid for r in picks}) == 9
        assert len({slot_of[r.rid] for r in picks}) == 9


@pytest.mark.parametrize("config", sorted(config_limits()))
def test_control_fails(tiny_spec, config):
    """The float8 control, in the program's place, reads a widest gap over
    the configuration's limit; the sound run reads under it."""
    limit = config_limits()[config]
    _, done = serve_tiny(tiny_spec, TINY_CHAT, SEED)
    reqs = check.sample(done, SEED, 6)
    served, state = check.served_gaps(tiny_spec, SEED, reqs)
    control = check.control_gaps(tiny_spec, state)
    assert served.max() <= limit
    assert control.max() > limit, (served.max(), control.max())
    assert np.mean(control > 0) > np.mean(served > 0)
