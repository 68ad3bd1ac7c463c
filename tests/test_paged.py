"""Paged KV-cache pool tests: pool-manager accounting, paged-vs-dense
engine equivalence, COW prefix sharing, page-quota queue-on-exhaustion,
page-copy hand-off, monitor occupancy — plus the engine lifecycle
satellites (queue pruning, not-drained signal, in-flight cancel)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import ClusterSpec, Hypervisor
from repro.layers import attention as attn
from repro.models import get_model
from repro.runtime import BatchingEngine, GatewayFleet, ServingGateway
from repro.runtime.paged import PagePoolManager


@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(get_config("smollm-135m")).replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=n).tolist()


# ---------------------------------------------------------------------------
# PagePoolManager (pure host control plane)
# ---------------------------------------------------------------------------

def test_pool_alloc_free_refcount():
    pool = PagePoolManager(n_pages=9, page_size=4, n_slots=2, max_blocks=8)
    assert pool.total_pages == 8 and pool.free_pages == 8
    plan = pool.admit(0, "a", list(range(10)))       # 3 blocks (pos 0..9)
    assert len(plan.blocks) == 3 and plan.write_start == 0
    assert pool.used_pages == 3 and pool.tenant_pages("a") == 3
    assert list(pool.block_tables[0][:3]) == plan.blocks
    pool.release_slot(0)
    assert pool.used_pages == 0 and pool.tenant_pages("a") == 0
    assert pool.block_tables[0].sum() == 0


def test_pool_prefix_share_and_cow():
    pool = PagePoolManager(n_pages=17, page_size=4, n_slots=3, max_blocks=8)
    toks = list(range(11))                           # 2 full blocks + tail
    a = pool.admit(0, "t", toks)
    b = pool.admit(1, "t", toks)
    # b shares a's 2 full blocks AND the exact-content tail page
    assert b.matched_pages == 3 and b.skip_prefill
    assert b.blocks == a.blocks
    assert pool.used_pages == 3                      # one physical copy
    # write into the shared tail forces a COW detach for the writer
    assert pool.is_shared(0, 2)
    src, dst = pool.cow(0, 2, "t")
    assert src == a.blocks[2] and dst != src
    assert not pool.is_shared(0, 2) and pool.cow_copies == 1
    # a context differing only in its FINAL token still shares the tail:
    # position n-1 is written by decode, not prefill, so written content
    # is identical
    c = pool.admit(2, "t", toks[:-1] + [99])
    assert c.matched_pages == 3 and c.skip_prefill
    # ...but a context differing at a WRITTEN tail position shares only
    # the full blocks
    pool.release_slot(2)
    d = pool.admit(2, "t", toks[:-2] + [99, 10])
    assert d.matched_pages == 2 and not d.skip_prefill
    assert d.blocks[:2] == a.blocks[:2] and d.blocks[2] not in (src, dst)


def test_pool_sharing_is_tenant_scoped():
    pool = PagePoolManager(n_pages=17, page_size=4, n_slots=2, max_blocks=8)
    toks = list(range(9))
    a = pool.admit(0, "alice", toks)
    b = pool.admit(1, "bob", toks)
    assert b.matched_pages == 0
    assert not set(a.blocks) & set(b.blocks)
    assert pool.tenant_pages("alice") == 3 and pool.tenant_pages("bob") == 3


def test_pool_admit_exhaustion_rolls_back_cleanly():
    """admit() hitting NoPagesError mid-allocation must free the pages it
    already popped (and undo shared increfs) — no silent pool shrink."""
    from repro.runtime.paged import NoPagesError
    pool = PagePoolManager(n_pages=5, page_size=4, n_slots=2, max_blocks=8)
    pool.admit(0, "t", list(range(7)))               # 2 of 4 pages
    free_before = pool.free_pages
    with pytest.raises(NoPagesError):
        pool.admit(1, "u", list(range(10)))          # needs 3, only 2 free
    assert pool.free_pages == free_before
    assert pool.tenant_pages("u") == 0


def test_pool_pages_needed_counts_sharing():
    pool = PagePoolManager(n_pages=17, page_size=4, n_slots=2, max_blocks=8)
    toks = list(range(11))
    assert pool.pages_needed("t", toks) == 3
    pool.admit(0, "t", toks)
    assert pool.pages_needed("t", toks) == 0         # fully shareable now
    assert pool.pages_needed("t", toks, share=False) == 3
    assert pool.pages_needed("other", toks) == 3


# ---------------------------------------------------------------------------
# Paged decode sweep == whole-table attention
# ---------------------------------------------------------------------------

PS, NB = 4, 16            # page size and block-table columns (64 positions)


def _whole_table(p, x, positions, cache, block_tables, opts):
    """The paged decode's attention over every column of the block table,
    dequantised up front, in one softmax: the sweep's oracle."""
    B = x.shape[0]
    q, _, _ = attn._qkv(p, x, positions, opts)
    k, v = cache["k"][block_tables], cache["v"][block_tables]
    if "k_scale" in cache:
        k = attn._deq(k, cache["k_scale"][block_tables], x.dtype)
        v = attn._deq(v, cache["v_scale"][block_tables], x.dtype)
    k = k.reshape((B, -1) + k.shape[3:])
    v = v.reshape((B, -1) + v.shape[3:])
    kpos = cache["pos"][block_tables].reshape(B, -1)
    mask = attn._causal_mask(positions, kpos, opts.window, opts.causal,
                             k_valid=kpos >= 0)
    y = attn._attend(q, k, v, mask, opts)
    return jnp.einsum("bshgk,hgkd->bsd", y, p["wo"].astype(x.dtype))


def _paged_batch(lens, opts, quant, seed=0):
    """Random bf16 params and pool; row b holds ``lens[b]`` live positions
    (0 = inactive row: pos -1, an all-null block table) on pages of its
    own in every column, unwritten positions at pos -1."""
    B, kv, hd = len(lens), opts.n_kv_heads, opts.head_dim
    d = opts.n_heads * hd
    kp, kx, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = attn.init_attention(kp, d, opts, jnp.bfloat16)
    x = jax.random.normal(kx, (B, 1, d), jnp.bfloat16)
    n_pages = 1 + B * NB
    pool = attn.init_paged_kv_pool(n_pages, PS, opts, jnp.bfloat16, quant)
    k = jax.random.normal(kk, pool["k"].shape, jnp.float32)
    v = jax.random.normal(kv_, pool["v"].shape, jnp.float32)
    if quant:
        pool["k"], pool["k_scale"] = attn._quant_rows(k)
        pool["v"], pool["v_scale"] = attn._quant_rows(v)
    else:
        pool["k"], pool["v"] = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    tables = np.zeros((B, NB), np.int32)
    kpos = np.full((n_pages, PS), -1, np.int32)
    for b, n in enumerate(lens):
        if n:
            tables[b] = 1 + b * NB + np.arange(NB)
            for t in range(n - 1):               # the step writes n - 1
                kpos[tables[b, t // PS], t % PS] = t
    pool["pos"] = jnp.asarray(kpos)
    pos = np.array([n - 1 if n else -1 for n in lens], np.int32)
    return p, x, pool, tables, pos


# live lengths per row (0 = inactive) cover 1, ps - 1, C, C + 1 and
# nb * ps - 1 positions, C = 4 pages = 16 positions a chunk
@pytest.mark.parametrize("lens,window,kv,g,quant,cap,chunk", [
    ((1, 3, 16, 17), 0, 4, 1, False, 0.0, 4),
    ((63, 0, 17, 1), 0, 2, 3, False, 0.0, 4),
    ((63, 50, 0, 36), 20, 4, 1, False, 0.0, 4),    # windows from 16 on
    ((35, 63, 50, 0), 20, 2, 3, True, 0.0, 4),     # windows from 15 on
    ((40, 16, 0, 2), 0, 4, 1, False, 30.0, 4),
    ((63, 50, 45, 0), 20, 2, 3, True, 0.0, 3),     # table padded to 18
    ((17, 63, 1, 0), 0, 2, 3, False, 0.0, NB),     # one pass
], ids=["global-mha", "global-gqa-inactive", "window-past-first-chunk",
        "window-int8", "softcap", "int8-ragged-chunk", "one-pass"])
def test_paged_sweep_matches_whole_table(monkeypatch, lens, window, kv, g,
                                         quant, cap, chunk):
    """``attn_decode_paged`` sweeping ``chunk`` columns at a time agrees
    with one softmax over the whole table, within bf16 rounding; the
    columns it reads are exactly those ``swept_cols`` counts for the
    engine: a NaN planted in V poisons the output iff its column is
    swept."""
    opts = attn.AttnOpts(n_heads=kv * g, n_kv_heads=kv, head_dim=8,
                         window=window, softcap=cap)
    p, x, pool, tables, pos = _paged_batch(lens, opts, quant)
    B, itemsize = len(lens), 1 if quant else 2
    per_col = 2 * B * PS * kv * opts.head_dim * itemsize
    monkeypatch.setattr(attn, "SWEEP_CHUNK_BYTES", chunk * per_col)
    assert attn.sweep_chunk_cols(B, NB, PS, kv, opts.head_dim,
                                 itemsize) == chunk
    step = jax.jit(lambda pool, bt: attn.attn_decode_paged(
        p, x, jnp.asarray(pos)[:, None], pool, bt, opts))
    bt = jnp.asarray(tables)
    out, written = step(pool, bt)
    want = _whole_table(p, x, jnp.asarray(pos)[:, None], written, bt, opts)
    live = pos >= 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=3e-2, rtol=3e-2)

    lo, hi = attn.sweep_chunks(jnp.asarray(pos), PS, NB, window, chunk)
    assert (int(lo), int(hi)) == tuple(
        int(b) for b in attn.sweep_chunks(pos, PS, NB, window, chunk, np))
    leaf = "v_scale" if quant else "v"
    swept = []
    for col in range(NB):
        bad = pool[leaf].at[tables[live, col]].set(jnp.nan)
        out, _ = step(dict(pool, **{leaf: bad}), bt)
        if np.isnan(np.asarray(out, np.float32)[live]).any():
            swept.append(col)
    live_pos = pos[live]
    first = min(np.maximum(live_pos - window + 1, 0)) if window else 0
    c0 = first // PS // chunk * chunk
    c1 = min(NB, (max(live_pos) // PS // chunk + 1) * chunk)
    assert swept == list(range(c0, c1))
    assert len(swept) == attn.swept_cols(pos, PS, NB, window, chunk)
    assert c0 == int(lo) * chunk


@pytest.mark.parametrize("chunked", [False, True])
def test_sweep_counts_on_decode_span(served_model, monkeypatch, tmp_path,
                                     chunked):
    """The engine stamps the decode dispatch span with the block-table
    columns the sweep covers at the step's positions, summed over the
    layers: the whole table when it is one chunk, else the chunks up to
    the furthest position. Chunked or not, the tokens are the same."""
    from repro.core import spans
    cfg, model, params = served_model
    prompt = _prompt(cfg, 20)

    def serve():
        eng = BatchingEngine(model, params, n_slots=2, max_len=64,
                             paged=True, page_size=16)
        req = eng.submit(prompt, max_new_tokens=6)
        assert eng.run_until_idle() is True
        return req.out_tokens

    unchunked = serve()
    if chunked:      # one 16-position page a chunk
        per_col = 2 * 2 * 16 * cfg.n_kv_heads * cfg.resolved_head_dim * 4
        monkeypatch.setattr(attn, "SWEEP_CHUNK_BYTES", per_col)
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = serve()
    finally:
        jax.profiler.stop_trace()
    got = [s.attrs for s in spans.recorded()
           if s.name == "rc3e.engine.decode_dispatch"]
    spans.clear()
    assert out == unchunked
    # positions 19..24 lie in the table's second of four columns
    swept = 2 if chunked else 4
    assert len(got) == 6 and all(
        a == {"table_cols_swept": swept * cfg.n_layers,
              "table_cols": 4 * cfg.n_layers} for a in got)


# ---------------------------------------------------------------------------
# Paged engine == dense engine
# ---------------------------------------------------------------------------

def test_paged_engine_matches_dense(served_model):
    """Same greedy tokens with the page pool as with dense per-slot rows —
    prompt lengths straddle page boundaries (ps=16) and pad buckets."""
    cfg, model, params = served_model
    prompts = [_prompt(cfg, n, seed=n) for n in (2, 5, 15, 16, 17, 31, 33)]

    def serve(**kw):
        eng = BatchingEngine(model, params, n_slots=3, max_len=64, **kw)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert eng.run_until_idle() is True
        assert all(r.finish_reason == "length" for r in reqs)
        return [r.out_tokens for r in reqs]

    assert serve() == serve(paged=True, page_size=16)


def test_paged_engine_int8_pool_matches_dense_int8(served_model):
    """kv_quant engines agree paged-vs-dense (int8 pools + scales page)."""
    cfg, model, params = served_model
    qcfg = cfg.replace(kv_quant=True)
    qmodel = get_model(qcfg)
    prompts = [_prompt(cfg, n, seed=100 + n) for n in (5, 17, 23)]

    def serve(**kw):
        eng = BatchingEngine(qmodel, params, n_slots=2, max_len=64, **kw)
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        assert eng.run_until_idle() is True
        return [r.out_tokens for r in reqs]

    assert serve() == serve(paged=True, page_size=16)


def test_cow_branches_decode_independently(served_model):
    """Two branches share prompt pages; after one finishes, the survivor
    keeps decoding correct tokens (COW detached its tail page)."""
    cfg, model, params = served_model
    prompt = _prompt(cfg, 34, seed=7)      # 2 full blocks + 1-token tail

    eng = BatchingEngine(model, params, n_slots=2, max_len=64, paged=True,
                        page_size=16)
    short = eng.submit(prompt, max_new_tokens=2, tenant="t")
    long = eng.submit(prompt, max_new_tokens=8, tenant="t")
    assert eng.run_until_idle() is True
    assert eng.pool.stats()["prefix_hits"] >= 3      # 2 full + tail shared
    assert eng.pool.stats()["cow_copies"] >= 1

    solo = BatchingEngine(model, params, n_slots=1, max_len=64)
    ref = solo.submit(prompt, max_new_tokens=8)
    solo.run_until_idle()
    assert long.out_tokens == ref.out_tokens
    assert short.out_tokens == ref.out_tokens[:2]
    # all pages returned once both branches finished
    assert eng.pool.used_pages == 0


def test_page_exhaustion_queues_not_oom(served_model):
    """A pool smaller than the offered load defers admissions (and preempts
    when growth fails) instead of erroring — every request completes."""
    cfg, model, params = served_model
    eng = BatchingEngine(model, params, n_slots=4, max_len=64, paged=True,
                        page_size=16, cache_pages=5)      # 4 usable pages
    reqs = [eng.submit(_prompt(cfg, 20, seed=i), max_new_tokens=20)
            for i in range(4)]
    assert eng.run_until_idle(max_steps=5000) is True
    assert all(len(r.out_tokens) == 20 for r in reqs)
    assert all(r.finish_reason == "length" for r in reqs)


def test_tenant_page_budget_queues(served_model):
    """A tenant at its page budget queues while another tenant's requests
    flow — per-tenant accounting of the shared memory fabric."""
    cfg, model, params = served_model
    eng = BatchingEngine(model, params, n_slots=4, max_len=64, paged=True,
                        page_size=16, cache_pages=17)
    eng.set_tenant_pages("greedy", 2)
    g1 = eng.submit(_prompt(cfg, 20, seed=1), max_new_tokens=4,
                    tenant="greedy")     # needs 2 pages: fills the budget
    g2 = eng.submit(_prompt(cfg, 20, seed=2), max_new_tokens=4,
                    tenant="greedy")     # must wait for g1's pages
    other = eng.submit(_prompt(cfg, 20, seed=3), max_new_tokens=4,
                       tenant="other")
    eng.step()
    assert eng.active_by_tenant() == {"greedy": 1, "other": 1}
    assert eng.queued_by_tenant() == {"greedy": 1}
    assert eng.run_until_idle() is True
    assert all(len(r.out_tokens) == 4 for r in (g1, g2, other))


def test_submit_rejects_impossible_request(served_model):
    cfg, model, params = served_model
    eng = BatchingEngine(model, params, n_slots=2, max_len=64, paged=True,
                        page_size=16, cache_pages=3)      # 2 usable pages
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(_prompt(cfg, 40, seed=0), max_new_tokens=20)
    # a big pool doesn't help when the BLOCK TABLE can't hold the context:
    # this must reject at submit, not explode inside step() (regression)
    eng2 = BatchingEngine(model, params, n_slots=2, max_len=64, paged=True,
                         page_size=16, cache_pages=33)    # 32 usable pages
    with pytest.raises(ValueError, match="blocks"):
        eng2.submit(_prompt(cfg, 70, seed=0), max_new_tokens=4)
    ok = eng2.submit(_prompt(cfg, 10, seed=1), max_new_tokens=4)
    assert eng2.run_until_idle() is True
    assert len(ok.out_tokens) == 4


# ---------------------------------------------------------------------------
# Engine lifecycle satellites
# ---------------------------------------------------------------------------

def test_run_until_idle_signals_stall(served_model):
    """max_steps expiring with queued work returns False — a stall is not
    silently mistaken for completion."""
    cfg, model, params = served_model
    eng = BatchingEngine(model, params, n_slots=2, max_len=64)
    for i in range(3):
        eng.submit(_prompt(cfg, 5, seed=i), max_new_tokens=8)
    assert eng.run_until_idle(max_steps=2) is False
    assert eng.run_until_idle() is True


def test_cancel_in_flight_frees_slot_and_pages(served_model):
    """cancel() releases an in-flight request's slot and pool pages
    immediately (a timed-out client must not burn a slot until
    max_new_tokens) and stamps finish_reason."""
    cfg, model, params = served_model
    eng = BatchingEngine(model, params, n_slots=2, max_len=64, paged=True,
                        page_size=16)
    victim = eng.submit(_prompt(cfg, 17, seed=0), max_new_tokens=40)
    other = eng.submit(_prompt(cfg, 5, seed=1), max_new_tokens=4)
    for _ in range(2):
        eng.step()
    assert victim in eng.inflight()
    pages_before = eng.pool.used_pages
    assert eng.cancel(victim) is True
    assert victim.done.is_set() and victim.finish_reason == "cancelled"
    assert victim not in eng.inflight()
    assert eng.pool.used_pages < pages_before
    assert eng.cancel(victim) is False               # already finished
    assert eng.run_until_idle() is True
    assert other.finish_reason == "length"


def test_cancel_queued_request(served_model):
    cfg, model, params = served_model
    eng = BatchingEngine(model, params, n_slots=1, max_len=64)
    first = eng.submit(_prompt(cfg, 5, seed=0), max_new_tokens=3)
    queued = eng.submit(_prompt(cfg, 5, seed=1), max_new_tokens=3)
    assert eng.cancel(queued) is True
    assert queued.finish_reason == "cancelled" and not queued.out_tokens
    assert eng.run_until_idle() is True
    assert first.finish_reason == "length"
    assert eng.queued_by_tenant() == {}              # pruned, not zeroed


def test_finish_reason_eos(served_model):
    cfg, model, params = served_model
    eng = BatchingEngine(model, params, n_slots=1, max_len=64)
    probe = eng.submit(_prompt(cfg, 6, seed=2), max_new_tokens=8)
    eng.run_until_idle()
    eos = probe.out_tokens[0]
    eng2 = BatchingEngine(model, params, n_slots=1, max_len=64, eos_id=eos)
    req = eng2.submit(_prompt(cfg, 6, seed=2), max_new_tokens=8)
    eng2.run_until_idle()
    assert req.finish_reason == "eos"
    assert req.out_tokens == [eos]


# ---------------------------------------------------------------------------
# Control plane: gateway grants, monitor occupancy, fleet hand-off
# ---------------------------------------------------------------------------

def test_gateway_page_grants_and_monitor_occupancy(served_model):
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=1,
                                cache_pages_per_device=64))
    gw = ServingGateway(hv, model, params, n_slots=4, max_len=64, paged=True)
    sess = gw.open_session("acme", slots=2)
    vs = hv.db.find_slice(sess.slice_id)
    assert vs.cache_pages == gw._session_page_grant(2)
    assert hv.db.page_grants()                        # device-level metering
    gw.submit("acme", _prompt(cfg, 17, seed=0), max_new_tokens=4)
    gw.step()
    pages = hv.status()["pages"]
    assert pages and next(iter(pages.values()))["used"] > 0
    assert gw.run_until_idle() is True
    gw.close()


def test_fleet_handoff_copies_pages(served_model):
    """A directed migration moves an in-flight request by copying its pool
    pages — decode continues without prefix replay and the final tokens
    match an unmigrated run."""
    cfg, model, params = served_model
    prompt = _prompt(cfg, 20, seed=5)

    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fl = GatewayFleet(hv, model, params, n_slots=4, max_len=64, paged=True)
    fl.open_session("a", slots=2)
    req = fl.submit("a", prompt, max_new_tokens=12)
    for _ in range(3):
        fl.step()
    prefix = list(req.out_tokens)
    assert hv.migrate_slice(fl.session("a").slice_id,
                            target_device="dev-0-1") is not None
    assert fl.handoffs[-1]["page_copied"] == 1
    assert fl.handoffs[-1]["replayed_inflight"] == 0
    assert fl.run_until_idle() is True
    assert req.out_tokens[:len(prefix)] == prefix

    hv2 = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=1))
    fl2 = GatewayFleet(hv2, model, params, n_slots=4, max_len=64, paged=True)
    fl2.open_session("a", slots=2)
    ref = fl2.submit("a", prompt, max_new_tokens=12)
    assert fl2.run_until_idle() is True
    assert req.out_tokens == ref.out_tokens
    fl.close()
    fl2.close()


def test_elastic_page_pressure_scales_out(served_model):
    """A page-pressured device triggers elastic scale-out to a PARKED one;
    the hand-off carries the page-hungriest tenant's traffic."""
    cfg, model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fl = GatewayFleet(hv, model, params, n_slots=2, max_len=64, paged=True,
                      cache_pages=9, autoscale_every=1, page_pressure=0.5)
    fl.open_session("big", slots=1)
    fl.open_session("small", slots=1)
    assert len(fl._engines) == 1                     # packed on one device
    fl.submit("big", _prompt(cfg, 33, seed=0), max_new_tokens=16)
    fl.submit("small", _prompt(cfg, 17, seed=1), max_new_tokens=8)
    for _ in range(6):
        fl.step()
    assert len(fl._engines) == 2, "page pressure should wake dev-0-1"
    woke = [e for e in hv.log if e["kind"] == "elastic_page_pressure"]
    assert woke
    assert fl.run_until_idle() is True
    fl.close()
