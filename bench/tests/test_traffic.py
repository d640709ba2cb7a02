"""The generator: same seed, same requests; another seed, the same sizes
and gaps with other token ids (in a closed loop, dealt to other clients)."""
import numpy as np
import pytest

import traffic
from spec import load_json

BIG = 2**40 + 12345


@pytest.mark.parametrize("mix_name", ["chat"])
def test_open_loop_same_work_every_seed(mix_name):
    mix = load_json("traffic", mix_name)
    a = traffic.open_loop(mix, BIG, 51.0, 1000)
    b = traffic.open_loop(mix, BIG, 51.0, 1000)
    c = traffic.open_loop(mix, 7, 51.0, 1000)
    assert [(x.max_new, x.at_s) for x in a] == [(x.max_new, x.at_s) for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    # one fixed schedule: the seed draws only the token ids
    assert [(len(x.prompt), x.max_new, x.at_s) for x in a] == \
        [(len(x.prompt), x.max_new, x.at_s) for x in c]
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, c))
    assert len(set(np.round(np.diff([x.at_s for x in a]), 6))) > 1
    assert len(a) == round(mix["rate_per_s"] * 51.0)
    assert a[0].at_s == 0.0 and a[-1].at_s < 51.0
    p, o = mix["prompt"], mix["output"]
    assert all(p["min"] <= len(x.prompt) <= p["max"]
               and len(x.prompt) % p["multiple"] == 0 for x in a)
    assert all(o["min"] <= x.max_new <= o["max"] for x in a)
    assert abs(np.median([len(x.prompt) for x in a]) - p["median"]) <= 16


def test_closed_loop_blocks_hold_the_same_sizes():
    mix = load_json("traffic", "decode")
    blk = mix["block"]
    for clients in (8, 16):
        la = traffic.ClosedLoop(mix, BIG, 1000, clients)
        lc = traffic.ClosedLoop(mix, 3, 1000, clients)
        # four rounds of sends, one request per client in each
        a = [la.next(c) for _ in range(4) for c in range(clients)]
        c = [lc.next(k) for _ in range(4) for k in range(clients)]
        for i in range(0, len(a), clients):
            assert sorted((x.max_new, len(x.prompt)) for x in a[i:i + clients]) \
                == sorted((x.max_new, len(x.prompt)) for x in c[i:i + clients])
        assert [x.max_new for x in a] != [x.max_new for x in c]
        assert not all((x.prompt[:4] == y.prompt[:4]).all()
                       for x, y in zip(a, c) if len(x.prompt) == len(y.prompt))
        lo, hi = mix["output"]["min"], mix["output"]["max"]
        assert all(lo <= x.max_new <= hi for x in a)
        assert {len(x.prompt) for x in a} == set(mix["prompt"]["values"])
        assert [x.rid for x in a] == list(range(4 * clients))
        # each client walks the whole output distribution in one block
        one = [la.next(0).max_new for _ in range(blk)]
        assert sorted(one) == sorted(traffic.quantiles(mix["output"], blk))


def test_quantiles_lognormal_is_clipped_and_rounded():
    d = {"dist": "lognormal", "median": 160, "sigma": 0.6, "min": 64,
         "max": 512, "multiple": 16}
    q = traffic.quantiles(d, 101)
    assert q == sorted(q) and q[50] == 160 and min(q) >= 64 and max(q) <= 512
    assert all(v % 16 == 0 for v in q)
