"""The generator: bit-identical per seed, and its rate, lengths and
tenant shares are those its mix file states, for every seed."""
import json
import pathlib

import numpy as np
import pytest

import traffic

MIXES = sorted((pathlib.Path(__file__).resolve().parents[1] / "traffic")
               .glob("*.json"))


def _mix(path):
    return json.loads(path.read_text())


def _key(arrivals):
    return [(a.due_s, a.tenant, a.prompt.tobytes(), a.max_new)
            for a in arrivals]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_schedule(path):
    mix = _mix(path)
    a = traffic.schedule(mix, 2**33 + 7, 20, 4, 1000)
    b = traffic.schedule(mix, 2**33 + 7, 20, 4, 1000)
    assert _key(a) == _key(b)
    c = traffic.schedule(mix, 2**33 + 8, 20, 4, 1000)
    assert _key(a) != _key(c)
    # seeds past 32 bits stay distinct
    d = traffic.schedule(mix, 7, 20, 4, 1000)
    assert _key(a) != _key(d)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_work(path):
    mix = _mix(path)
    seconds, tenants = 30, 8
    runs = [traffic.schedule(mix, s, seconds, tenants, 1000)
            for s in (1, 2**31 + 5)]
    n = int(mix["rate_rps"] * seconds)
    for arr in runs:
        assert len(arr) == n
        due = np.array([a.due_s for a in arr])
        assert np.all(np.diff(due) > 0) and due[-1] <= seconds
        assert due[-1] == pytest.approx(n / mix["rate_rps"])
        lens = np.array([len(a.prompt) for a in arr])
        outs = np.array([a.max_new for a in arr])
        p, o = mix["prompt_tokens"], mix["output_tokens"]
        assert lens.min() >= p["min"] and lens.max() <= p["max"]
        assert outs.min() >= o["min"] and outs.max() <= o["max"]
        assert abs(np.median(lens) - p["median"]) <= 0.05 * p["median"]
        assert abs(np.median(outs) - o["median"]) <= 0.05 * o["median"]
        counts = np.bincount([a.tenant for a in arr], minlength=tenants)
        assert counts.max() - counts.min() <= 1      # equal popularity
        assert all(a.prompt.dtype == np.int32 for a in arr)
    for f in (lambda a: a.max_new, lambda a: len(a.prompt),
              lambda a: a.tenant):
        assert sorted(map(f, runs[0])) == sorted(map(f, runs[1]))
    gaps = [np.sort(np.diff([0.0] + [a.due_s for a in arr])) for arr in runs]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9, atol=1e-12)


def test_zipf_burst_and_prefix():
    mix = {"arrivals": "poisson", "rate_rps": 10.0,
           "prompt_tokens": {"median": 64, "sigma": 0.5, "min": 40,
                             "max": 200},
           "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 30},
           "tenants": {"zipf_s": 1.1},
           "burst": {"factor": 3, "on_s": 2, "off_s": 8},
           "prefix_tokens": 32}
    arr = traffic.schedule(mix, 3, 40, 4, 500)
    counts = np.bincount([a.tenant for a in arr], minlength=4)
    assert list(counts) == sorted(counts, reverse=True) and counts[0] > \
        2 * counts[3]
    due = np.array([a.due_s for a in arr])
    assert np.all(np.diff(due) >= 0)
    on = np.sum((due % 10) < 2)
    assert on > 0.3 * len(due)            # ON is 20% of the time
    for t in range(4):
        heads = {a.prompt[:32].tobytes() for a in arr if a.tenant == t}
        assert len(heads) == 1


def test_fixed_order_changes_only_the_tokens():
    mix = {**_mix(MIXES[0]), "order": "fixed"}
    a, b = (traffic.schedule(mix, s, 30, 2, 1000) for s in (3, 2**31 + 5))
    assert [(x.due_s, x.tenant, len(x.prompt), x.max_new) for x in a] == \
        [(x.due_s, x.tenant, len(x.prompt), x.max_new) for x in b]
    assert _key(a) != _key(b)


def test_unknown_process_is_refused():
    with pytest.raises(ValueError):
        traffic.schedule({"arrivals": "closed"}, 1, 10, 1, 100)


def test_warmup_stays_inside_the_pool():
    import warmup
    mix = {"prompt_tokens": {"min": 256, "max": 2048},
           "output_tokens": {"max": 128}}
    dep = {"page_size": 16, "max_len": 4096, "n_slots": 4}
    assert warmup.shapes(mix, {**dep, "cache_pages": 289})["scrub"] == \
        list(range(1, 289))
    full = warmup.shapes(mix, {**dep, "cache_pages": None})
    assert full["scrub"] == list(range(1, 4 * 136 + 1))
    assert max(nb for _, nb in full["splice"]) == 128
    small = warmup.shapes(mix, {**dep, "cache_pages": 101})
    assert max(nb for _, nb in small["splice"]) == 100


def test_knee_is_the_last_rate_held():
    import sweep
    assert sweep.holds(4, 7) and not sweep.holds(4, 8)
    rows = [{"rate_rps": r, "held": h}
            for r, h in ((0.8, False), (0.4, True), (0.6, True), (1.0, True))]
    assert sweep.knee(rows) == 0.6
    assert sweep.knee(rows[:1]) is None
