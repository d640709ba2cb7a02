"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the longest, is run through the reference
(``reference.py``) over its prompt and its served tokens, teacher-forced.
At every served position the gap by which the served token's reference
logit lies below the reference's best is divided by the spread (standard
deviation) of that position's reference logits, so that one limit holds
at any width.  The number compared is the widest such gap,
``max_gap_sigma``; the limit is the configuration's
``check.max_gap_sigma``.

The control reads, at the same positions, the gap of the token that the
reference computed in float8 puts first (``control_gaps``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import reference
from weights import seed_key


def sample(finished: Sequence, seed: int, n: int,
           slot_of: Optional[Dict[int, int]] = None) -> List:
    """Up to ``n`` finished requests: the longest, then one from each serving
    slot in turn (``slot_of`` maps a request to the slot that served it),
    slots and requests drawn from the seed.  So the sample spreads over as
    many slots as it can, and a fault in some of the batch's rows shows."""
    reqs = sorted(finished, key=lambda r: r.rid)
    if not reqs:
        return []
    slot_of = slot_of or {}
    longest = max(reqs, key=lambda r: (len(r.prompt) + len(r.out_tokens),
                                       -r.rid))
    rng = np.random.default_rng([int(seed) % 2**63, 0x5EED])
    by_slot: Dict[int, List] = {}
    for r in reqs:
        if r is not longest:
            by_slot.setdefault(slot_of.get(r.rid, 0), []).append(r)
    home = slot_of.get(longest.rid, 0)
    slots = [int(k) for k in rng.permutation(sorted(by_slot))]
    groups = [[by_slot[k][i] for i in rng.permutation(len(by_slot[k]))]
              for k in sorted(slots, key=lambda k: k == home)]
    picks = [longest]
    while len(picks) < n and any(groups):
        for g in groups:
            if g and len(picks) < n:
                picks.append(g.pop())
    return picks


def teacher_forced(reqs: Sequence, length: int):
    """(tokens, targets), each (B, length): the prompt and the served
    tokens but the last, and at each position the served token due next
    (-1 at positions that served nothing)."""
    tokens = np.zeros((len(reqs), length), np.int32)
    targets = np.full((len(reqs), length), -1, np.int32)
    for b, r in enumerate(reqs):
        prompt = np.asarray(r.prompt, np.int32)
        out = np.asarray(r.out_tokens, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        tokens[b, :len(seq)] = seq
        targets[b, len(prompt) - 1:len(prompt) - 1 + len(out)] = out
    return tokens, targets


def _gaps(best, tgt, spread, targets):
    mask = targets >= 0
    return ((best - tgt) / spread)[mask]


def served_gaps(spec, seed: int, reqs: Sequence):
    """Reference gap (in spreads) of every served token of ``reqs``, and
    the f32 hidden states that the control reuses."""
    key = seed_key(seed)
    tokens, targets = teacher_forced(reqs, spec.max_len)
    x = reference.hidden(spec, key, tokens)
    best, tgt, spread, _ = reference.head(spec, key, x, targets)
    return _gaps(best, tgt, spread, targets), (key, tokens, targets, x)


def control_gaps(spec, state, rounding: str = "fp8"):
    """Reference gap of the token that ``rounding``'s forward puts first at
    each served position."""
    key, tokens, targets, x32 = state
    x_low = reference.hidden(spec, key, tokens, rounding)
    *_, top_low = reference.head(spec, key, x_low, targets, rounding)
    top_low = np.where(targets >= 0, top_low, -1)
    best, tgt, spread, _ = reference.head(spec, key, x32, top_low)
    return _gaps(best, tgt, spread, top_low)


def judge(spec, seed: int, finished: Sequence, n_requests: int,
          slot_of: Optional[Dict[int, int]] = None) -> dict:
    """Sample, compare and decide.  Returns the numbers compared, each
    with its limit, and ``correct``."""
    reqs = sample(finished, seed, n_requests, slot_of)
    bad = sum(int(((np.asarray(r.out_tokens) < 0)
                   | (np.asarray(r.out_tokens) >= spec.vocab)).sum())
              for r in reqs)
    if not reqs or bad:
        return {"correct": False, "requests": len(reqs), "tokens": 0,
                "bad_tokens": bad, "max_gap_sigma": None,
                "limit": spec.max_gap_sigma}
    gaps, _ = served_gaps(spec, seed, reqs)
    worst = float(gaps.max())
    return {"correct": bool(np.isfinite(worst)
                            and worst <= spec.max_gap_sigma),
            "requests": len(reqs), "tokens": int(gaps.size),
            "bad_tokens": 0, "max_gap_sigma": worst,
            "limit": spec.max_gap_sigma}
