"""The one traffic generator.  A mix is a JSON file under ``bench/traffic/``
that only sets parameters; this module reads every mix.

Every seed gets the same sizes and gaps: lengths are quantiles of the
mix's distribution at (i + 0.5) / n, and the seed draws the token ids (and
in a closed loop deals the clients their rows).  So two seeds do the same
work, and what differs between them is content.

Loops:

* ``open``: Poisson arrivals at ``rate_per_s``.  The gaps are quantiles of
  the exponential in one fixed shuffled order, scaled to fill the window
  exactly, as a replayed trace; each request is timed from its scheduled
  arrival.  The order is the same for every seed: at a load near the
  knee, a window of a few dozen requests queues by their order, so an
  order drawn from the seed would change the work.
* ``closed``: one client per serving slot; each sends its next request when
  its last one finishes.  Each client walks a row of one fixed table of
  ``block`` quantile sizes, and the seed only deals the rows to the
  clients.  The slots are alike, so every seed makes the engine do the
  same work at the same times; only the order of the first requests and
  the token ids differ.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""

    rid: int
    prompt: np.ndarray       # int32 token ids
    max_new: int
    at_s: float = 0.0        # scheduled arrival (open loop), from window start


def quantiles(dist: dict, n: int) -> List[int]:
    """n sizes at the quantiles (i + 0.5) / n of ``dist``."""
    kind = dist["dist"]
    qs = [(i + 0.5) / n for i in range(n)]
    if kind == "choice":
        vals = [dist["values"][i % len(dist["values"])] for i in range(n)]
    elif kind == "uniform":
        vals = [dist["min"] + (dist["max"] - dist["min"]) * q for q in qs]
    elif kind == "lognormal":
        nd = NormalDist()
        vals = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(q))
                for q in qs]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    mult = dist.get("multiple", 1)
    out = []
    for v in vals:
        v = min(max(v, dist.get("min", v)), dist.get("max", v))
        v = int(round(v / mult)) * mult
        out.append(min(max(v, dist.get("min", v)), dist.get("max", v)))
    return out


def _rng(seed: int, salt: int):
    return np.random.default_rng([int(seed) % 2**63, salt])


def _tokens(seed: int, rid: int, n: int, vocab: int) -> np.ndarray:
    return _rng(seed, 1_000_003 + rid).integers(0, vocab, n).astype(np.int32)


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """Every request of an open-loop window, in arrival order."""
    rate = mix["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    rng = _rng(0, 11)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plens = rng.permutation(quantiles(mix["prompt"], n))
    outs = rng.permutation(quantiles(mix["output"], n))
    return [Item(i, _tokens(seed, i, int(plens[i]), vocab), int(outs[i]),
                 float(arrivals[i])) for i in range(n)]


class ClosedLoop:
    """The requests of ``clients`` closed-loop clients.

    Row ``j`` of the table sends, as its ``k``-th request, the prompt size
    of quantile ``(j + k) % block`` and the output size of quantile
    ``(j + stride * k) % block``, ``stride`` prime to ``block``, so that a
    row walks the whole distribution.  The clients take rows spread evenly
    over the table, dealt by the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int, clients: int):
        self.block = mix["block"]
        self.plens = quantiles(mix["prompt"], self.block)
        self.outs = quantiles(mix["output"], self.block)
        self.stride = next(s for s in range(max(2, self.block * 3 // 8),
                                            self.block + 2)
                           if math.gcd(s, self.block) == 1)
        rows = [c * self.block // clients for c in range(clients)]
        self.rows = [int(r) for r in _rng(seed, 13).permutation(rows)]
        self.sent = [0] * clients
        self.seed, self.vocab, self.rid = seed, vocab, 0

    def next(self, client: int) -> Item:
        """Client ``client``'s next request."""
        j, k = self.rows[client], self.sent[client]
        self.sent[client] += 1
        p = self.plens[(j + k) % self.block]
        o = self.outs[(j + self.stride * k) % self.block]
        item = Item(self.rid, _tokens(self.seed, self.rid, p, self.vocab), o)
        self.rid += 1
        return item


def longest(mix: dict) -> int:
    """Largest prompt plus output the mix can send."""
    return mix["prompt"].get("max", max(mix["prompt"].get("values", [0]))) \
        + mix["output"]["max"]
