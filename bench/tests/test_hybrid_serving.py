"""The served hybrid (Mamba-2 beside attention, two periods of the
granite-4.0-h layout at a small width) against the float32 reference of
``bench/arch/granite_hybrid.py`` on the CPU: the paged engine's logits,
not its tokens, after a bucket-padded prefill and many decode steps;
the state a padded prefill leaves; a recycled slot; preemption; a fleet
hand-off; and an admission whose pages all match a live prefix.

The program runs in float32 on the reference's weights, so the logits
differ only by the order of the arithmetic: the chunked SSD scan against
the reference's sequential recurrence, and the fused reductions of XLA.
Those
differ by 1e-6 to 4e-5 of their largest value after the 20 layers
(most in the heads whose state sums hundreds of positions); recurrent
state or activations held in bfloat16 instead differ by about 1e-3 of it
(2 ** -8 per rounding). The tolerances sit between the two."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spec
import weights
from repro.core import ClusterSpec, Hypervisor
from repro.models import get_model
from repro.runtime import BatchingEngine, GatewayFleet, serve

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures" / \
    "tiny-hybrid" / "bench" / "configs" / "tiny-hybrid.json"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
SEED = 2**31 + 5
# largest |program - reference| over the largest |reference|, of the
# logits or of a state leaf; the same bound holds between two ways the
# program serves one request (a prefill against decode steps, a padded
# prefill against an unpadded one), whose arithmetic differs only in the
# order of f32 sums
TOL = 2e-4


@pytest.fixture(scope="module")
def hybrid():
    conf = json.loads(FIXTURE.read_text())
    model = dict(conf["model"], layer_types=PERIOD * 2,
                 num_hidden_layers=2 * len(PERIOD))
    arch = spec.arch(conf)
    m = arch.sizes("granite-2-periods", model)
    lay = arch.layout(m)
    cfg = arch.program(m).replace(dtype="float32", param_dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make_params(lay, SEED, jax.devices()[0]))
    return arch, m, get_model(cfg), params


def _reference(hybrid, toks):
    """Reference logits at every position of ``toks``."""
    arch, m, _, _ = hybrid
    lay = arch.layout(m)
    x = arch.embed(m, weights.top(lay, SEED), jnp.asarray(toks, jnp.int32))
    for r in range(m.n_layers):
        x = arch.layer(m, r, weights.layer(lay, SEED, *arch.layer_at(m, r)),
                       x, None)
    return np.asarray(arch.logits(m, weights.top(lay, SEED), x, None))


def _recording(seen):
    """A ``use_program`` wrapper: every decode call files each live row's
    logits under (request id, position of its input token)."""
    def wrap(eng, compiled):
        def step(params, caches, tokens, pos, tables):
            logits, caches = compiled(params, caches, tokens, pos, tables)
            for i, p in enumerate(np.asarray(pos)):
                r = eng._slots[i]
                if p >= 0 and r is not None:
                    seen[(r.request_id, int(p))] = np.asarray(logits[i, 0])
            return logits, caches
        return step
    return wrap


def _engine(hybrid, seen, **kw):
    _, _, model, params = hybrid
    kw = {"n_slots": 2, "max_len": 128, "page_size": 8, **kw}
    eng = BatchingEngine(model, params, paged=True, **kw)
    eng.use_program(_recording(seen)(eng, eng._decode))
    return eng


def _prompt(m, n, seed):
    return np.random.default_rng(seed).integers(0, m.vocab, n)


def _against_reference(hybrid, req, seen):
    toks = np.concatenate([req.prompt, req.out_tokens])
    ref = _reference(hybrid, toks)
    rows = sorted(p for rid, p in seen if rid == req.request_id)
    got = np.stack([seen[(req.request_id, p)] for p in rows])
    err = np.abs(got - ref[rows]).max() / np.abs(ref).max()
    return rows, err


def _state(eng):
    return [leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(eng.caches)[0]
            if serve._is_state(path)]


def test_padded_prefill_then_decode_matches_reference(hybrid):
    """Prompts of 37 and 21 tokens prefill in buckets of 64 and 32 (with
    padding after the real tokens), then decode 18 tokens each."""
    m = hybrid[1]
    seen = {}
    eng = _engine(hybrid, seen)
    reqs = [eng.submit(_prompt(m, n, i), max_new_tokens=18)
            for i, n in enumerate((37, 21))]
    assert eng.run_until_idle()
    for req in reqs:
        rows, err = _against_reference(hybrid, req, seen)
        assert rows == list(range(len(req.prompt) - 1,
                                  len(req.prompt) + 17))
        assert err < TOL, err


def test_padded_prefill_state_equals_unpadded(hybrid):
    """The prefill program's recurrent state and conv tail at a bucket of
    64 holding 37 tokens equal those of the 37 tokens alone."""
    _, m, model, params = hybrid
    prefill = serve._prefill_jit(model, 128, full_len=True)
    ctx = _prompt(m, 37, 3).astype(np.int32)
    padded = np.full((1, 64), -1, np.int32)
    padded[0, :37] = ctx
    _, a = prefill(params, jnp.asarray(padded))
    _, b = prefill(params, jnp.asarray(ctx[None]))
    unpadded = dict(jax.tree_util.tree_flatten_with_path(b)[0])
    leaves = [(x, unpadded[path]) for path, x in
              jax.tree_util.tree_flatten_with_path(a)[0]
              if serve._is_state(path)]
    # state and conv of the period's 9 Mamba-2 positions, each stacked
    # over the 2 periods
    assert len(leaves) == 2 * 9
    for x, y in leaves:
        assert np.abs(x - y).max() / np.abs(y).max() < TOL


def test_recycled_slot_matches_fresh_engine_and_is_zeroed(hybrid):
    """One slot serves a long request, then a two-token prompt whose one
    context token decodes on top of whatever the slot's state row holds:
    its logits equal a fresh engine's only if release zeroed the row."""
    m = hybrid[1]
    seen, fresh_seen = {}, {}
    eng = _engine(hybrid, seen, n_slots=1)
    eng.submit(_prompt(m, 40, 7), max_new_tokens=6)
    assert eng.run_until_idle()
    assert all(not np.asarray(s).any() for s in _state(eng))
    second = eng.submit(_prompt(m, 2, 8), max_new_tokens=6)
    assert eng.run_until_idle()
    fresh = _engine(hybrid, fresh_seen, n_slots=1)
    alone = fresh.submit(_prompt(m, 2, 8), max_new_tokens=6)
    assert fresh.run_until_idle()
    assert second.out_tokens == alone.out_tokens
    for p in range(7):
        np.testing.assert_array_equal(seen[(second.request_id, p)],
                                      fresh_seen[(alone.request_id, p)])
    assert all(not np.asarray(s).any() for s in _state(eng))


def test_preemption_resumes_with_the_same_logits(hybrid):
    """A request preempted mid-decode (its slot, pages and state row taken
    back) resumes by re-prefilling its prompt and the tokens it had made,
    and decodes on with the logits of an uninterrupted run."""
    m = hybrid[1]
    prompts = [_prompt(m, 20, 10 + i) for i in range(2)]

    def serve_all(preempt):
        seen = {}
        eng = _engine(hybrid, seen)
        reqs = [eng.submit(p, max_new_tokens=30) for p in prompts]
        for _ in range(10):
            eng.step()
        if preempt:
            eng._preempt(0)
        assert eng.run_until_idle()
        return eng, reqs, seen

    cut, reqs, seen = serve_all(True)
    assert cut.preemptions == 1
    _, whole, whole_seen = serve_all(False)
    for a, b in zip(reqs, whole):
        assert a.out_tokens == b.out_tokens
        for p in range(len(a.prompt) - 1, len(a.prompt) + 29):
            x, y = seen[(a.request_id, p)], whole_seen[(b.request_id, p)]
            assert np.abs(x - y).max() / np.abs(y).max() < TOL


def test_pool_exhaustion_preempts_and_resumes(hybrid):
    """With 8 usable pages, two requests that each grow to 7 cannot both
    stay seated: the growth that finds the pool empty preempts one, which
    re-prefills its context once pages free and makes the tokens and
    logits of a run with pages to spare."""
    m = hybrid[1]
    prompts = [_prompt(m, 20, 10 + i) for i in range(2)]

    def serve_all(cache_pages):
        seen = {}
        eng = _engine(hybrid, seen, cache_pages=cache_pages)
        reqs = [eng.submit(p, max_new_tokens=30) for p in prompts]
        eng.step()
        assert eng.active_by_tenant() == {"default": 2}
        assert eng.run_until_idle()
        return eng, reqs, seen

    tight, reqs, seen = serve_all(9)
    assert tight.preemptions >= 1
    roomy, whole, whole_seen = serve_all(None)
    assert roomy.preemptions == 0
    for a, b in zip(reqs, whole):
        assert a.out_tokens == b.out_tokens
        for p in range(len(a.prompt) - 1, len(a.prompt) + 29):
            x, y = seen[(a.request_id, p)], whole_seen[(b.request_id, p)]
            assert np.abs(x - y).max() / np.abs(y).max() < TOL


def test_fleet_handoff_carries_the_state(hybrid, monkeypatch):
    """A tenant moves to the other device mid-decode: its pages and its
    slot's state are copied, and decoding continues there with the logits
    it would have had unmoved."""
    _, m, model, params = hybrid
    record = {}
    real = serve.BatchingEngine.use_program
    monkeypatch.setattr(
        serve.BatchingEngine, "use_program",
        lambda self, compiled: real(self, _recording(record["seen"])(
            self, compiled)))
    prompts = [_prompt(m, 30, 20 + i) for i in range(2)]

    def run_fleet(migrate):
        record["seen"] = seen = {}
        hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
        fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=128,
                             paged=True, page_size=8)
        t = fleet.open_session("t", slots=2, service_model="raas")
        reqs = [fleet.submit("t", p, max_new_tokens=12) for p in prompts]
        for _ in range(4):
            fleet.step()
        if migrate:
            dst = next(d for d in hv.db.devices if d != fleet.device_of("t"))
            hv.migrate_slice(t.slice_id, target_device=dst, reason="test")
            assert fleet.handoffs[-1]["page_copied"] == 2
        assert fleet.run_until_idle()
        fleet.close()
        return reqs, seen

    moved, seen = run_fleet(True)
    still, still_seen = run_fleet(False)
    for a, b in zip(moved, still):
        assert a.out_tokens == b.out_tokens
        for p in range(len(a.prompt) - 1, len(a.prompt) + 11):
            np.testing.assert_allclose(seen[(a.request_id, p)],
                                       still_seen[(b.request_id, p)],
                                       rtol=0, atol=1e-6)


def test_prefix_matched_admission_still_prefills(hybrid):
    """Two requests of one tenant with one prompt: the second's pages all
    match the first's, but a page holds no recurrent state, so it is
    prefilled like the first and agrees with the reference."""
    m = hybrid[1]
    seen = {}
    eng = _engine(hybrid, seen)
    prompt = _prompt(m, 33, 30)
    reqs = [eng.submit(prompt, max_new_tokens=8) for _ in range(2)]
    assert eng.run_until_idle()
    assert eng.pool.prefix_hits == 0
    assert reqs[0].out_tokens == reqs[1].out_tokens
    for req in reqs:
        _, err = _against_reference(hybrid, req, seen)
        assert err < TOL, err
