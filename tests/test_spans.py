"""Host spans on the serving path: off without a profiler session, the
span tree of a paged fleet round under one, and the same tokens either
way; the scrub's counters in the operator's status."""
import glob

import jax
import pytest

from repro.configs import get_config, reduced
from repro.core import ClusterSpec, Hypervisor, spans
from repro.models import get_model
from repro.runtime import GatewayFleet

PROMPTS = {"a": [list(range(1, 12)), list(range(3, 9))],
           "b": [list(range(20, 45)), [7, 8, 9]]}

ENGINE_PHASES = {"rc3e.engine.scrub", "rc3e.engine.pick",
                 "rc3e.engine.admit", "rc3e.engine.prepare_writes",
                 "rc3e.engine.decode_dispatch", "rc3e.engine.readback",
                 "rc3e.engine.emit"}
ROUND_CHILDREN = {"rc3e.fleet.begin_round", "rc3e.fleet.engine_step",
                  "rc3e.fleet.journal_sync", "rc3e.fleet.finish_round"}


@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(get_config("smollm-135m")).replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _serve(served_model):
    """Two tenants on a two-device paged fleet, every request to the end;
    returns (fleet, requests)."""
    model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64,
                         paged=True, page_size=8)
    for t in PROMPTS:
        fleet.open_session(t, slots=1, service_model="raas")
    reqs = [fleet.submit(t, p, max_new_tokens=4)
            for t, ps in PROMPTS.items() for p in ps]
    assert fleet.run_until_idle()
    return fleet, reqs


def test_no_profiler_session_records_no_span(served_model):
    spans.clear()
    fleet, reqs = _serve(served_model)
    assert spans.recorded() == []
    assert spans.span("rc3e.fleet.round") is spans.span("rc3e.engine.emit")
    assert all(r.submitted_at <= r.admitted_at <= r.first_token_at
               for r in reqs)
    fleet.close()


def _pad(n, min_cache_len):
    bucket = 8
    while bucket < n:
        bucket *= 2
    return max(n, min(bucket, min_cache_len))


def test_span_tree_of_a_paged_fleet(served_model, tmp_path):
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        fleet, reqs = _serve(served_model)
    finally:
        jax.profiler.stop_trace()
    rec = spans.recorded()
    name = [s.name for s in rec]
    parent = [None if s.parent is None else name[s.parent] for s in rec]
    rounds = [i for i, n in enumerate(name) if n == "rc3e.fleet.round"]
    assert rounds and all(rec[i].parent is None for i in rounds)
    for i, n in enumerate(name):
        if n in ROUND_CHILDREN:
            assert parent[i] == "rc3e.fleet.round", n
        elif n in ("rc3e.engine.prefill", "rc3e.engine.splice"):
            assert parent[i] == "rc3e.engine.admit", n
        elif n == "rc3e.engine.scrub":
            assert parent[i] in ("rc3e.fleet.engine_step",
                                 "rc3e.engine.admit",
                                 "rc3e.engine.prepare_writes")
        elif n in ENGINE_PHASES:
            assert parent[i] == "rc3e.fleet.engine_step", n
        else:
            assert n == "rc3e.fleet.round"
            assert rec[i].parent is None
        s = rec[i]
        assert s.t0 <= s.t1
        if s.parent is not None:
            p = rec[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1
    for i in rounds:        # begin, the engine steps, finish, in order
        kids = [name[k] for k, s in enumerate(rec) if s.parent == i]
        assert kids[0] == "rc3e.fleet.begin_round"
        assert kids[-1] == "rc3e.fleet.finish_round"
        assert set(kids[1:-1]) <= {"rc3e.fleet.engine_step",
                                   "rc3e.fleet.journal_sync"}
    for s in rec:
        if s.name == "rc3e.fleet.engine_step":
            assert s.attrs["chip"] == fleet.jax_device(s.attrs["device"]).id
    decoded = [k for k, s in enumerate(rec) if s.name == "rc3e.engine.readback"]
    assert decoded and all(name[rec[k].parent] == "rc3e.fleet.engine_step"
                           for k in decoded)

    by_id = {r.request_id: r for r in reqs}
    admits = [s.attrs["request"] for s in rec if s.name == "rc3e.engine.admit"]
    assert sorted(admits) == sorted(by_id)
    for r in by_id.values():
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
    eng = fleet.engine_for("a")
    prefills = [s for s in rec if s.name == "rc3e.engine.prefill"]
    assert prefills
    for s in prefills:
        ctx = len(by_id[s.attrs["request"]].prompt) - 1
        assert s.attrs["tokens"] == ctx
        assert s.attrs["padded"] == _pad(ctx, eng._min_cache_len)
        assert rec[s.parent].attrs["request"] == s.attrs["request"]
    writes = [s.attrs for s in rec if s.name == "rc3e.engine.prepare_writes"]
    assert writes and all(set(a) == {"grown", "cow", "preempted"}
                          for a in writes)
    assert sum(a["grown"] for a in writes) > 0
    # the spans are in the profiler's trace too
    from jax.profiler import ProfileData
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    traced = {e.name for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events}
    assert {"rc3e.fleet.round", "rc3e.fleet.engine_step",
            "rc3e.engine.readback", "rc3e.engine.prefill"} <= traced
    fleet.close()
    spans.clear()


def test_tokens_identical_with_spans_on_and_off(served_model, tmp_path):
    off_fleet, off = _serve(served_model)
    off_fleet.close()
    jax.profiler.start_trace(str(tmp_path))
    try:
        on_fleet, on = _serve(served_model)
    finally:
        jax.profiler.stop_trace()
    on_fleet.close()
    spans.clear()
    assert [r.out_tokens for r in on] == [r.out_tokens for r in off]
    assert all(len(r.out_tokens) == 4 for r in on)


def test_scrub_status_counts_pages_and_dispatches(served_model):
    fleet, _ = _serve(served_model)
    status = fleet.hv.monitor.status()["scrub"]
    assert status
    for dev, s in status.items():
        eng = fleet._engines[dev]
        assert s == {"pages": eng.pool.pages_scrubbed,
                     "dispatches": eng.scrub_dispatches}
        stats = eng.page_stats()
        assert stats["scrub_dispatches"] == eng.scrub_dispatches
        assert "scrub_ms" not in stats
    assert sum(s["pages"] for s in status.values()) > 0
    assert sum(s["dispatches"] for s in status.values()) > 0
    fleet.close()
