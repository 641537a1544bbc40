"""The request generator: seeded, the same work for every seed, and the
declared medians and clips."""
from pathlib import Path

import numpy as np
import pytest

import traffic

BENCH = Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _mix(name):
    return traffic.load_json(BENCH / "traffic" / f"{name}.json")


def _cell(mix):
    return {"rate_rps": 2.0} if mix["arrival"] == "poisson" else {}


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.make_requests(mix, _cell(mix), 2**31 + 11, 30, 1000)
    b = traffic.make_requests(mix, _cell(mix), 2**31 + 11, 30, 1000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due) == (y.max_new, y.due)


@pytest.mark.parametrize("name,fixed", [(m, f) for m in MIXES
                                        for f in (True, False)])
def test_every_seed_gets_the_same_work(name, fixed):
    """With the mix's ``order_seed`` every seed replays one trace and only
    the token ids differ; without it, the order differs as well."""
    mix = _mix(name)
    if not fixed:
        mix.pop("order_seed", None)
    a = traffic.make_requests(mix, _cell(mix), 1, 30, 1000)
    b = traffic.make_requests(mix, _cell(mix), 2, 30, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    same = [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert same == (fixed and "order_seed" in mix)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    if mix["arrival"] == "poisson":
        ga = np.diff([r.due for r in a])
        gb = np.diff([r.due for r in b])
        assert abs(ga.sum() - gb.sum()) < 2.0 / _cell(mix)["rate_rps"]


@pytest.mark.parametrize("name", MIXES)
def test_medians_and_clips_as_declared(name):
    mix = _mix(name)
    reqs = traffic.make_requests(mix, {"rate_rps": 20.0}, 5, 50, 1000)
    for key, got in (("prompt", [len(r.prompt) for r in reqs]),
                     ("output", [r.max_new for r in reqs])):
        spec = mix[key]
        assert min(got) >= spec["min"] and max(got) <= spec["max"]
        med = float(np.median(got))
        if spec["min"] < spec["median"] < spec["max"]:
            assert abs(med - spec["median"]) <= 0.02 * spec["median"] + 1
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)


def test_poisson_arrivals_inside_the_window_at_the_rate():
    mix = _mix("conversation")
    reqs = traffic.make_requests(mix, {"rate_rps": 2.0}, 9, 50, 1000)
    dues = [r.due for r in reqs]
    assert len(reqs) == 100
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 50


def test_backlog_is_queued_before_the_window():
    mix = dict(_mix("conversation"), arrival="backlog", requests=24)
    reqs = traffic.make_requests(mix, {}, 3, 50, 1000)
    assert len(reqs) == 24
    assert all(r.due is None for r in reqs)


def test_chunk_lengths_and_first_chunks():
    reqs = [traffic.Planned(0, np.zeros(70, np.int32), 1, 0.0),
            traffic.Planned(1, np.zeros(20, np.int32), 1, 0.0)]
    assert traffic.first_chunks(reqs, 32) == {32, 20}
    assert traffic.chunk_lengths(reqs, 32) == {1, 32, 6}
    assert [traffic.pow2(n) for n in (1, 2, 3, 6, 32, 33)] == \
        [1, 2, 4, 8, 32, 64]


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert traffic.percentile(vals, 90) == 90
    assert traffic.percentile(vals, 95) == 95
    assert traffic.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        traffic.percentile([], 50)



@pytest.mark.parametrize("n,k", [(31, 5), (26, 5), (30, 5), (4, 5), (10, 1)])
def test_balanced_order_takes_every_stratum_once_per_run(n, k):
    order = traffic.balanced_order(np.random.default_rng(2**31 + 7), n, k)
    assert sorted(order.tolist()) == list(range(n))
    k = min(k, n)
    bounds = [round(s * n / k) for s in range(k + 1)]
    stratum = np.searchsorted(bounds, order, side="right") - 1
    full = min(b - a for a, b in zip(bounds, bounds[1:]))
    for b in range(full):
        assert sorted(stratum[b * k:(b + 1) * k].tolist()) == list(range(k))
