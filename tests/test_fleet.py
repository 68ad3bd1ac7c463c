"""Serving fleet tests: placement that follows the DeviceDB, live session
hand-off on straggler migration (queued + in-flight requests complete on
the target engine, generated tokens preserved, quota balanced), and the
elastic scale-up / park lifecycle."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import ClusterSpec, DeviceState, Hypervisor
from repro.models import get_model
from repro.rc2f import AdmissionError
from repro.runtime import GatewayFleet


@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(get_config("smollm-135m")).replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, n=6, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=n).tolist()


def _flag_straggler(hv, hot_slice, cold_slices, n=8):
    """Inject telemetry so exactly ``hot_slice`` trips the straggler policy."""
    for _ in range(n):
        hv.monitor.record_step(hot_slice, 400.0)
        for sid in cold_slices:
            hv.monitor.record_step(sid, 1.0)


# ---------------------------------------------------------------------------
# Placement
# ---------------------------------------------------------------------------

def test_sessions_decode_on_their_slices_device(served_model):
    """One engine per device actually hosting tenants; a tenant's requests
    run on the engine backing its vSlice's device."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64)
    # 4 + 2 slots overflow the first device: placement must span both
    a = fleet.open_session("a", slots=2)
    b = fleet.open_session("b", slots=2)
    c = fleet.open_session("c", slots=2, service_model="raas")
    devs = {t: hv.db.find_slice(s.slice_id).device_id
            for t, s in (("a", a), ("b", b), ("c", c))}
    assert devs["a"] == devs["b"] != devs["c"]
    assert set(fleet._engines) == set(devs.values())
    for t in ("a", "b", "c"):
        assert fleet.device_of(t) == devs[t]
        fleet.submit(t, _prompt(cfg, seed=ord(t)), max_new_tokens=3)
    fleet.step()
    assert fleet.engine_for("a") is fleet.engine_for("b")
    assert fleet.engine_for("c") is not fleet.engine_for("a")
    assert fleet.engine_for("c").active_by_tenant() == {"c": 1}
    fleet.run_until_idle()
    assert all(s["served"] == 1 for s in fleet.stats().values())
    fleet.close()


def test_fleet_engines_share_one_decode_program(served_model):
    """The decode executable is compiled once; every further engine is a PR
    cache hit binding the same fingerprint."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64)
    fleet.open_session("a", slots=4, service_model="rsaas")
    fleet.open_session("b", slots=4, service_model="rsaas")
    ups = [e for e in hv.log if e["kind"] == "engine_up"]
    assert len(ups) == 2 and all(u["cache_hit"] for u in ups)
    assert {u["fingerprint"] for u in ups} == {fleet.program_fingerprint}
    fleet.close()


def test_fleet_rejects_ssm_before_any_allocation():
    """The engine-layout restriction (SSM state lives only beside a paged
    pool) must surface at construction, not from lazy engine creation
    inside open_session (which would strand an admitted tenant and its
    vSlice)."""
    cfg = reduced(get_config("mamba2-370m")).replace(dtype="float32")
    model = get_model(cfg)
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=1))
    with pytest.raises(ValueError, match="paged"):
        GatewayFleet(hv, model, model.init(jax.random.PRNGKey(0)))
    assert all(u == 0.0 for u in hv.db.utilization().values())


def test_session_share_scales_with_engine_width(served_model):
    """A vSlice holds k of a device's four slots. On an engine of 16
    slots its session may hold 4k of them at once, with the same share of
    the page pool; on an engine of at most four it holds k, as before."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=1))
    fleet = GatewayFleet(hv, model, params, n_slots=16, max_len=64,
                         paged=True, page_size=16, cache_pages=65)
    fleet.open_session("a", slots=1, service_model="raas")
    eng = fleet.engine_for("a")
    assert eng._tenant_share["a"] == 4 and eng._tenant_pages["a"] == 16
    for i in range(6):
        fleet.submit("a", _prompt(cfg, seed=i), max_new_tokens=4)
    fleet.step()
    assert eng.active_by_tenant()["a"] == 4
    assert fleet.run_until_idle()
    fleet.close()
    narrow = GatewayFleet(Hypervisor(ClusterSpec(n_nodes=1,
                                                 devices_per_node=1)),
                          model, params, n_slots=2, max_len=64)
    narrow.open_session("b", slots=1)
    assert narrow.engine_for("b")._tenant_share["b"] == 1
    narrow.close()


def test_open_session_failure_unwinds_allocation(served_model, monkeypatch):
    """If anything after the vSlice allocation fails (engine spin-up,
    program swap), open_session must return the quota and the slice."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=1))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64)
    monkeypatch.setattr(fleet, "_ensure_engine",
                        lambda dev: (_ for _ in ()).throw(
                            RuntimeError("device wedged")))
    with pytest.raises(RuntimeError, match="device wedged"):
        fleet.open_session("t", slots=1)
    assert hv.admission.usage("t")["slots"] == 0
    assert all(u == 0.0 for u in hv.db.utilization().values())
    monkeypatch.undo()
    fleet.open_session("t", slots=1)            # clean retry succeeds
    fleet.close()


def test_fleet_empty_prompt_rejected(served_model):
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=1))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64)
    fleet.open_session("t", slots=1)
    with pytest.raises(AdmissionError, match="empty prompt"):
        fleet.submit("t", [], max_new_tokens=4)
    assert hv.admission.usage("t")["inflight"] == 0
    fleet.close()


# ---------------------------------------------------------------------------
# Live migration hand-off
# ---------------------------------------------------------------------------

def test_migrated_tenant_decodes_on_target_engine(served_model):
    """THE fix this PR exists for: after migrate_stragglers flags a serving
    tenant, its subsequent decode steps execute on the TARGET device's
    engine — queued and in-flight requests complete there, the session
    rebinds, and the admission quota stays balanced."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64)
    hot = fleet.open_session("hot", slots=1)
    cold = fleet.open_session("cold", slots=1)
    old_slice, old_dev = hot.slice_id, fleet.device_of("hot")

    reqs = [fleet.submit("hot", _prompt(cfg, seed=i), max_new_tokens=8)
            for i in range(3)]                 # 1 in flight + 2 queued
    fleet.submit("cold", _prompt(cfg, seed=9), max_new_tokens=8)
    for _ in range(3):
        fleet.step()
    assert reqs[0].out_tokens and not reqs[0].done.is_set()
    mid_tokens = [list(r.out_tokens) for r in reqs]
    assert hv.admission.usage("hot")["inflight"] == 3

    _flag_straggler(hv, hot.slice_id, [cold.slice_id])
    moved = fleet.rebalance()
    assert moved and moved[0][0] == old_slice
    # session rebinds; new slice is on the other device, program carried
    assert hot.slice_id != old_slice
    new_vs = hv.db.find_slice(hot.slice_id)
    assert new_vs.device_id != old_dev
    assert new_vs.program == fleet.program_fingerprint
    assert fleet.handoffs[-1]["moved_requests"] == 3
    # quota survives the hand-off: the 3 requests are still in flight
    assert hv.admission.usage("hot")["inflight"] == 3

    # subsequent decode steps demonstrably run on the target engine
    source, target = fleet._engines[old_dev], fleet._engines[new_vs.device_id]
    steps_before = target.steps
    fleet.step()
    assert target.active_by_tenant().get("hot", 0) == 1
    assert "hot" not in source.active_by_tenant()
    assert "hot" not in source.queued_by_tenant()
    assert target.steps == steps_before + 1

    fleet.run_until_idle()
    assert all(len(r.out_tokens) == 8 for r in reqs)
    # tokens generated before the move survived it (prefix replay)
    for r, mid in zip(reqs, mid_tokens):
        assert r.out_tokens[:len(mid)] == mid
    assert hv.admission.usage("hot")["inflight"] == 0
    assert fleet.session("hot").served == 3
    fleet.close()


def test_handoff_tokens_match_unmigrated_run(served_model):
    """Greedy decode is deterministic: a migrated request must produce
    exactly the tokens it would have produced had it never moved."""
    cfg, model, params = served_model
    prompts = [_prompt(cfg, n=6, seed=i) for i in range(3)]

    def serve(migrate: bool):
        hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
        fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64)
        hot = fleet.open_session("hot", slots=1)
        cold = fleet.open_session("cold", slots=1)
        reqs = [fleet.submit("hot", p, max_new_tokens=8) for p in prompts]
        fleet.submit("cold", _prompt(cfg, seed=9), max_new_tokens=8)
        for _ in range(3):
            fleet.step()
        if migrate:
            _flag_straggler(hv, hot.slice_id, [cold.slice_id])
            fleet.rebalance()
            assert fleet.handoffs, "migration must have happened"
        fleet.run_until_idle()
        fleet.close()
        return [list(r.out_tokens) for r in reqs]

    assert serve(migrate=True) == serve(migrate=False)


def test_directed_migration_api(served_model):
    """Hypervisor.migrate_slice moves one slice to a named device and the
    fleet hands the dataplane off; target == source is a no-op."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64)
    t = fleet.open_session("t", slots=1)
    src = fleet.device_of("t")
    assert hv.migrate_slice(t.slice_id, target_device=src) is None
    dst = next(d for d in hv.db.devices if d != src)
    new = hv.migrate_slice(t.slice_id, target_device=dst, reason="ops")
    assert new is not None and new.device_id == dst
    assert fleet.device_of("t") == dst
    fleet.submit("t", _prompt(cfg), max_new_tokens=3)
    fleet.run_until_idle()
    assert fleet.session("t").served == 1
    fleet.close()


def test_cross_class_handoff_reresolves_geometry(served_model):
    """A hand-off between device CLASSES must re-resolve the tuned
    geometry on the destination: the target engine binds ITS class's
    winner from the ProgramCache tuned store (here seeded with two
    deliberately different geometries), pages cut at the source's page
    size are declined by the import guard (prefix replay instead), and
    the token stream stays bit-identical to an unmigrated default run."""
    from repro.tuning import TunedConfig, device_class, model_fingerprint
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2,
                                device_speeds=(1.0, 0.25)))
    fp = model_fingerprint(model.cfg, 64, True)
    hv.reconfig.cache.put_tuned(
        fp, device_class(1.0), TunedConfig(page_size=8,
                                           n_slots=4).to_dict())
    hv.reconfig.cache.put_tuned(
        fp, device_class(0.25), TunedConfig(decode_block_k=256,
                                            page_size=16,
                                            n_slots=2).to_dict())
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64,
                         paged=True, page_size=8, autotune=True)
    t = fleet.open_session("t", slots=1)
    src = fleet.device_of("t")
    assert fleet._engines[src].page_size == 8          # fast-class winner
    req = fleet.submit("t", _prompt(cfg), max_new_tokens=8)
    for _ in range(3):
        fleet.step()
    pre = list(req.out_tokens)
    assert pre and not req.done.is_set()

    dst = next(d for d in hv.db.devices if d != src)
    assert hv.db.devices[src].speed != hv.db.devices[dst].speed
    hv.migrate_slice(t.slice_id, target_device=dst, reason="ops")
    assert fleet.device_of("t") == dst
    # destination bound the 0.25x-class geometry, not the source's
    assert fleet._engines[dst].page_size == 16
    assert fleet._engines[dst].n_slots == 2
    ev = fleet.handoffs[-1]
    assert ev["src_geometry"] != ev["dst_geometry"]
    # page snapshot was cut at ps=8 — the ps=16 pool must decline it and
    # fall back to prefix replay (bit-exact greedy), never adopt raggedly
    assert ev["page_copied"] == 0 and ev["replayed_inflight"] == 1

    fleet.run_until_idle()
    assert req.out_tokens[:len(pre)] == pre            # tokens preserved
    assert len(req.out_tokens) == 8
    fleet.verify_invariants()
    fleet.close()

    # bit-exactness across the migration + both tuned geometries
    hv2 = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=1))
    fleet2 = GatewayFleet(hv2, model, params, n_slots=2, max_len=64,
                          paged=True, page_size=8)
    fleet2.open_session("t", slots=1)
    ref = fleet2.submit("t", _prompt(cfg), max_new_tokens=8)
    fleet2.run_until_idle()
    assert list(req.out_tokens) == list(ref.out_tokens)
    fleet2.close()


# ---------------------------------------------------------------------------
# Elastic scale-up / park lifecycle
# ---------------------------------------------------------------------------

def test_scale_up_wakes_parked_device_and_parks_after(served_model):
    """A deep aggregate backlog wakes a PARKED device and moves the
    deepest-queued tenant onto it; once drained and released, every device
    parks again and its engine is dropped."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64,
                         autoscale_every=1, scale_up_queue_depth=3)
    fleet.open_session("deep", slots=1)
    fleet.open_session("shallow", slots=1)
    assert fleet.device_of("deep") == fleet.device_of("shallow")
    assert hv.db.devices["dev-0-1"].state == DeviceState.PARKED

    reqs = [fleet.submit("deep", _prompt(cfg, seed=i), max_new_tokens=4)
            for i in range(6)]                       # backlog >= threshold
    fleet.submit("shallow", _prompt(cfg, seed=99), max_new_tokens=4)
    fleet.step()                                     # autoscale fires
    assert hv.db.devices["dev-0-1"].state == DeviceState.ACTIVE
    assert fleet.device_of("deep") == "dev-0-1"
    assert fleet.handoffs[-1]["tenant"] == "deep"
    scale_events = [e for e in hv.log if e["kind"] == "elastic_scale_out"]
    assert scale_events

    fleet.run_until_idle()
    assert all(len(r.out_tokens) == 4 for r in reqs)
    fleet.close_session("deep")
    fleet.close_session("shallow")
    # released devices park; the in-step autoscale reaps idle engines
    fleet.step()
    assert all(d.state == DeviceState.PARKED
               for d in hv.db.devices.values())
    assert fleet._engines == {}
    parked = [e for e in hv.log if e["kind"] == "engine_park"]
    assert len(parked) >= 2
    fleet.close()


def test_request_ids_unique_across_engines(served_model):
    """Engines share one fleet-level id stream: the hypervisor audit log
    keys serve events by request id, so ids from different devices must
    never collide."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64)
    fleet.open_session("a", slots=4, service_model="rsaas")
    fleet.open_session("b", slots=4, service_model="rsaas")
    assert fleet.device_of("a") != fleet.device_of("b")
    reqs = [fleet.submit(t, _prompt(cfg, seed=i), max_new_tokens=3)
            for i, t in enumerate(["a", "b"] * 3)]
    assert len({r.request_id for r in reqs}) == len(reqs)
    fleet.run_until_idle()
    serve_events = {e["request"] for e in hv.log if e["kind"] == "serve"}
    assert len(serve_events) == len(reqs)
    fleet.close()


def test_consolidate_infeasible_moves_nothing(served_model):
    """An infeasible drain is detected by the dry-run placement: no slice
    migrates (no tenant pays a hand-off) and False is returned."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64)
    a = fleet.open_session("a", slots=2)
    b = fleet.open_session("b", slots=2)
    c = fleet.open_session("c", slots=2, service_model="raas")  # dev 1
    dev0 = fleet.device_of("a")
    assert fleet.device_of("c") != dev0
    # dev 1 has 2 free slots; draining dev 0 needs 4 -> infeasible
    assert not fleet.elastic.consolidate(dev0)
    assert fleet.device_of("a") == fleet.device_of("b") == dev0
    assert not fleet.handoffs
    fleet.close()


def test_consolidate_drains_device_for_parking(served_model):
    """ElasticController.consolidate migrates every slice off a device
    (scale-in); the fleet follows with live hand-offs."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64)
    a = fleet.open_session("a", slots=4,             # fills dev 0
                           service_model="rsaas")
    b = fleet.open_session("b", slots=2)             # spills to dev 1
    dev_b = fleet.device_of("b")
    fleet.submit("b", _prompt(cfg), max_new_tokens=6)
    fleet.step()
    assert not fleet.elastic.consolidate(fleet.device_of("a")), \
        "a's 4-slot slice cannot fit next to b"
    fleet.close_session("a")
    assert fleet.elastic.consolidate(dev_b)          # b moves to dev 0
    assert fleet.device_of("b") != dev_b
    fleet.run_until_idle()
    assert fleet.session("b").served == 1
    fleet.park_idle_engines()
    assert list(fleet._engines) == [fleet.device_of("b")]
    fleet.close()


# ---------------------------------------------------------------------------
# Autoscale arbitration (one action per tick), SLO projection, down-ramp
# ---------------------------------------------------------------------------

def test_autoscale_one_action_when_multiple_signals_trip(served_model):
    """Regression: a burst wave trips queue depth AND page pressure on
    the same autoscale tick. Arbitration must act on exactly ONE signal —
    waking two devices for one overload would oscillate against the
    energy policy. With both signals hot and two PARKED devices
    available, one call wakes exactly one device."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=3))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64,
                         paged=True, page_size=4,
                         scale_up_queue_depth=2, page_pressure=0.8)
    fleet.open_session("a", slots=1)
    fleet.open_session("b", slots=1)
    dev0 = fleet.device_of("a")
    assert fleet.device_of("b") == dev0
    for i in range(8):                       # deep backlog: queue depth trips
        fleet.submit("a", _prompt(cfg, seed=i), max_new_tokens=4)
    hv.monitor.record_pages(dev0, 95, 100)   # page pressure trips too

    active_before = len([d for d in hv.db.devices.values()
                         if d.state == DeviceState.ACTIVE])
    woken = fleet.autoscale()
    active_after = len([d for d in hv.db.devices.values()
                        if d.state == DeviceState.ACTIVE])
    assert woken is not None
    assert active_after == active_before + 1, \
        "both signals tripped but exactly one device may wake per tick"
    assert len(fleet.autoscale_log) == 1
    assert fleet.autoscale_log[0]["signal"] == "queue_depth"
    fleet.run_until_idle()
    fleet.close()


def test_autoscale_slo_projection_wakes_before_queue_threshold(served_model):
    """The SLO signal acts on the arrival/service-rate TREND: with a
    backlog far below the queue-depth threshold but arrivals outrunning
    measured service capacity, the projected p95 breaches the SLO and a
    PARKED device wakes (signal = slo_projection)."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64,
                         scale_up_queue_depth=100,    # queue depth never trips
                         slo_p95_steps=8.0, slo_horizon=16)
    fleet.open_session("a", slots=1)
    fleet.open_session("b", slots=1)
    for i in range(4):                 # shallow backlog, but a real queue
        fleet.submit("a", _prompt(cfg, seed=i), max_new_tokens=4)
    # trend: 4 arrivals/step against 1 completion/device-step on 1 device
    for _ in range(8):
        hv.monitor.record_traffic(4, 1, 1)
    projected = fleet.elastic.projected_p95_steps(2, 16)
    assert projected is not None and projected > 8.0

    woken = fleet.autoscale()
    assert woken is not None
    assert fleet.autoscale_log[-1]["signal"] == "slo_projection"
    assert [e for e in hv.log if e["kind"] == "elastic_slo_scale_out"]
    assert hv.db.devices[woken].state == DeviceState.ACTIVE
    fleet.run_until_idle()
    fleet.close()


def test_autoscale_slo_quiet_trend_no_wake(served_model):
    """Under-SLO projection must NOT wake anything: same queue, but the
    measured service rate comfortably covers the arrivals."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=64,
                         scale_up_queue_depth=100,
                         slo_p95_steps=50.0, slo_horizon=4)
    fleet.open_session("a", slots=1)
    fleet.submit("a", _prompt(cfg), max_new_tokens=4)
    for _ in range(8):
        hv.monitor.record_traffic(1, 2, 1)   # mu covers lambda twice over
    assert fleet.autoscale() is None
    assert hv.db.devices["dev-0-1"].state == DeviceState.PARKED
    fleet.run_until_idle()
    fleet.close()


def test_downramp_consolidates_in_draw_order(served_model):
    """Diurnal down-ramp: with the backlog gone and the projection under
    margin, autoscale drains ONE device per tick, highest class draw
    first (3.0 parks before 2.0), re-packing tenants onto the cheap
    device; post-trough requests still complete within the SLO."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=3,
                                device_draws=(1.0, 3.0, 2.0)))
    fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64,
                         slo_p95_steps=20.0)
    for t in ("a", "b", "c", "d"):
        fleet.open_session(t, slots=1)
    assert len(fleet._engines) == 1          # pack-first: all on dev-0-0
    # burst half: spread the fleet across all three devices
    for t in ("a", "b"):
        assert fleet.elastic.scale_out(fleet.session(t).slice_id)
    assert len(set(fleet.device_of(t) for t in "abcd")) == 3
    assert hv.db.devices["dev-0-1"].draw == 3.0

    # trough: no queue, no trend -> one drain per tick, draw order
    drained1 = fleet._maybe_scale_in()
    assert drained1 == "dev-0-1", "the 3.0-draw device must park first"
    assert hv.db.devices["dev-0-1"].state == DeviceState.PARKED
    drained2 = fleet._maybe_scale_in()
    assert drained2 == "dev-0-2", "the 2.0-draw device parks second"
    assert fleet._maybe_scale_in() is None   # min_active floor holds
    assert [e["device"] for e in fleet.autoscale_log
            if e["action"] == "scale_in"] == ["dev-0-1", "dev-0-2"]
    assert all(fleet.device_of(t) == "dev-0-0" for t in "abcd")

    # through the trough the survivors still serve within the SLO
    start = fleet.steps
    reqs = [fleet.submit(t, _prompt(cfg, seed=ord(t)), max_new_tokens=4)
            for t in "abcd"]
    assert fleet.run_until_idle()
    assert all(len(r.out_tokens) == 4 for r in reqs)
    assert fleet.steps - start <= 20, "post-consolidation p95 within SLO"
    fleet.close()


def test_downramp_blocked_while_projection_above_margin(served_model):
    """Scale-in must NOT fire while the projected p95 sits above the
    scale-in margin — consolidating into a still-warm ramp would bounce
    straight back out."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=4, max_len=64,
                         slo_p95_steps=10.0, scale_in_margin=0.5)
    fleet.open_session("a", slots=1)
    fleet.open_session("b", slots=1)
    assert fleet.elastic.scale_out(fleet.session("a").slice_id)
    assert len(fleet._engines) == 2
    for _ in range(8):                       # projection ~ lambda*h/mu = 8
        hv.monitor.record_traffic(1, 1, 2)   # > margin (5) but under SLO
    assert fleet._maybe_scale_in() is None
    assert len(fleet._engines) == 2
    fleet.close()
