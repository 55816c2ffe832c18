"""The traffic generator: a seed fixes the trace; every seed gets the same
work on the mix's own schedule, with its own data."""

import numpy as np

from bench import data, generate
from bench import run as R


def _mix(name):
    return generate.load_mix(R.BENCH, name)


UNIFORM = data.data_set({"name": "uniform"})


def _ops(mix, seed, seconds=4.0):
    return generate.open_loop(mix, seconds, 10_000, data.rng(seed, 1),
                              UNIFORM[1])


def test_seed_fixes_the_trace():
    mix = _mix("churn-64-1024")
    a, b = _ops(mix, 2 ** 33 + 1), _ops(mix, 2 ** 33 + 1)
    assert [o.t for o in a] == [o.t for o in b]
    for x, y in zip(a, b):
        assert x.kind == y.kind
        for f in ("queries", "inserts", "deletes"):
            u, v = getattr(x, f), getattr(y, f)
            assert (u is None and v is None) or np.array_equal(u, v)


def test_every_seed_gets_the_same_work():
    mix = _mix("churn-64-1024")
    a, b = _ops(mix, 1), _ops(mix, 2)
    sizes = [sorted(len(o.queries) for o in ops if o.kind == "read")
             for ops in (a, b)]
    assert sizes[0] == sizes[1]
    assert sum(o.kind == "update" for o in a) == \
        sum(o.kind == "update" for o in b) > 0
    # one schedule, the mix's own: the same due times, kinds and sizes ...
    assert [o.t for o in a] == [o.t for o in b]
    assert [o.kind for o in a] == [o.kind for o in b]
    assert [len(o.queries) for o in a if o.kind == "read"] == \
        [len(o.queries) for o in b if o.kind == "read"]
    # ... holding the seed's own queries and update points
    x, y = (next(o for o in ops if o.kind == "read") for ops in (a, b))
    assert not np.array_equal(x.queries, y.queries)
    x, y = (next(o for o in ops if o.kind == "update") for ops in (a, b))
    assert not np.array_equal(x.inserts, y.inserts)
    # and another schedule seed gives another schedule
    c = _ops(dict(mix, schedule_seed=mix["schedule_seed"] + 1), 1)
    assert [o.t for o in c] != [o.t for o in a]


def test_sizes_rates_and_updates():
    mix = _mix("churn-64-1024")
    ops = _ops(mix, 3, seconds=10.0)
    reads = [o for o in ops if o.kind == "read"]
    assert len(reads) == round(mix["rate_per_s"] * 10.0)
    q = mix["read_queries"]
    for o in reads:
        n = len(o.queries)
        assert q["lo"] <= n <= q["hi"] and n % q["quantum"] == 0
        assert o.queries.min() >= 0.01 and o.queries.max() <= 0.99
    for o in ops:
        if o.kind == "update":
            assert len(o.deletes) == len(set(o.deletes)) == 1000
            assert o.deletes.max() < 10_000 and len(o.inserts) == 1000
    assert 8.0 < ops[-1].t < 11.0


def test_batch_sizes_cover_every_coalesced_batch():
    mix = _mix("served-128-2048")
    sizes = generate.batch_sizes(mix)
    assert sizes[0] == 128 and sizes[-1] == 4096
    assert all(b - a == 128 for a, b in zip(sizes, sizes[1:]))


def test_closed_loop_mix_and_seeded_data():
    assert _mix("batch-65536")["loop"] == "closed"
    points = UNIFORM[0]
    a = points(100, data.rng(-5, 0))
    assert np.array_equal(a, points(100, data.rng(-5, 0)))
    b = points(100, data.rng(-5, 1))
    assert not np.array_equal(a, b)
    # every seed has the unit square as its bounding box
    for p in (a, b):
        assert p[:, :2].min(axis=0).tolist() == [0.0, 0.0]
        assert p[:, :2].max(axis=0).tolist() == [1.0, 1.0]
