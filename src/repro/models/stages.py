"""Stage planner: compile a ModelConfig's per-layer pattern into grouped
``lax.scan`` stages so deep/heterogeneous stacks lower to small HLO.

A *site* is one layer's static description (mixer kind, mlp kind, rope theta,
window). Consecutive identical sites become a "run" stage (weights stacked over
the run, one scan). A repeating multi-site pattern (gemma2 local/global
alternation, zamba2 [5×ssm, shared-attn]) becomes a "pattern" stage: a scan
over repeats whose body unrolls one period.

Zamba2's shared attention block is one weight set applied at every
``shared_attn`` site (params live in ``params['shared']``, not in the stage).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, MIXER_SHARED_ATTN,
                                MIXER_SSM, ModelConfig)
from repro.layers.attention import (AttnOpts, attn_decode, attn_decode_paged,
                                    attn_forward, fill_kv_cache,
                                    init_attention, init_kv_cache,
                                    init_paged_kv_pool, sweep_chunk_cols)
from repro.layers.mla import (MLAOpts, fill_mla_cache, init_mla,
                              init_mla_cache, mla_decode, mla_forward)
from repro.layers.mlp import init_mlp, mlp_forward
from repro.layers.moe import MoEOpts, init_moe, moe_forward
from repro.layers.norms import init_rms_norm, rms_norm
from repro.layers.ssm import (SSMOpts, init_ssm, init_ssm_cache, ssm_decode,
                              ssm_forward)


# ---------------------------------------------------------------------------
# Static plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSite:
    mixer: str                  # global | local | ssm | shared_attn
    mlp: str                    # dense | moe | none
    d_ff: int = 0
    rope_theta: float = 10000.0
    window: int = 0

    @property
    def is_attn(self) -> bool:
        return self.mixer in (ATTN_GLOBAL, ATTN_LOCAL, MIXER_SHARED_ATTN)


@dataclasses.dataclass(frozen=True)
class Stage:
    kind: str                   # run | pattern
    sites: Tuple[LayerSite, ...]
    repeats: int


def _make_site(cfg: ModelConfig, i: int) -> LayerSite:
    mixer = cfg.layer_kinds()[i]
    if mixer == MIXER_SSM:
        if cfg.ssm.site_mlp:
            return LayerSite(mixer=mixer, mlp="dense", d_ff=cfg.d_ff)
        return LayerSite(mixer=mixer, mlp="none")
    theta = cfg.rope_theta
    window = 0
    if mixer == ATTN_LOCAL:
        window = cfg.window
        if cfg.rope_local_theta:
            theta = cfg.rope_local_theta
    if mixer == MIXER_SHARED_ATTN:
        return LayerSite(mixer=mixer, mlp="dense", d_ff=cfg.d_ff,
                         rope_theta=theta)
    if cfg.moe is not None:
        if i < cfg.moe.first_k_dense:
            return LayerSite(mixer, "dense", cfg.moe.dense_d_ff or cfg.d_ff,
                             theta, window)
        return LayerSite(mixer, "moe", 0, theta, window)
    return LayerSite(mixer, "dense", cfg.d_ff, theta, window)


def plan_stages(cfg: ModelConfig) -> Tuple[Stage, ...]:
    sites = [_make_site(cfg, i) for i in range(cfg.n_layers)]
    stages = []
    i = 0
    # prefix exceptions (e.g. deepseek first_k_dense) peel off as run stages
    k_dense = cfg.moe.first_k_dense if cfg.moe is not None else 0
    while i < k_dense:
        j = i
        while j < k_dense and sites[j] == sites[i]:
            j += 1
        stages.append(Stage("run", (sites[i],), j - i))
        i = j
    rest = sites[i:]
    p = len(cfg.pattern)
    reps, rem = divmod(len(rest), p)
    body = rest[: reps * p]
    if reps:
        period = tuple(rest[:p])
        assert body == list(period) * reps, "pattern does not tile layer list"
        if p == 1:
            stages.append(Stage("run", period, reps))
        else:
            stages.append(Stage("pattern", period, reps))
    j = i + reps * p
    while j < cfg.n_layers:
        k = j
        while k < cfg.n_layers and sites[k] == sites[j]:
            k += 1
        stages.append(Stage("run", (sites[j],), k - j))
        j = k
    return tuple(stages)


# ---------------------------------------------------------------------------
# Opts helpers
# ---------------------------------------------------------------------------

def attn_opts(cfg: ModelConfig, site: LayerSite) -> AttnOpts:
    g = cfg.geometry
    return AttnOpts(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, window=site.window, causal=cfg.causal,
        rope_theta=site.rope_theta, use_rope=cfg.use_rope,
        softcap=cfg.attn_softcap, qk_norm=cfg.qk_norm,
        query_scale=cfg.query_scale, attn_tp=cfg.attn_tp,
        decode_block_k=g.decode_block_k, flash_block_q=g.flash_block_q,
        flash_block_k=g.flash_block_k, kernel_force=g.kernel_force)


def mla_opts(cfg: ModelConfig) -> MLAOpts:
    return MLAOpts(n_heads=cfg.n_heads, cfg=cfg.mla,
                   rope_theta=cfg.rope_theta)


def ssm_opts(cfg: ModelConfig) -> SSMOpts:
    return SSMOpts(d_model=cfg.d_model, cfg=cfg.ssm,
                   tp=cfg.tp_mode == "tp", eps=cfg.norm_eps)


def moe_opts(cfg: ModelConfig) -> MoEOpts:
    return MoEOpts(cfg=cfg.moe, act=cfg.act, norm_topk=cfg.moe.norm_topk)


# ---------------------------------------------------------------------------
# Per-site init
# ---------------------------------------------------------------------------

def _init_site(cfg: ModelConfig, site: LayerSite, key, dtype):
    if site.mixer == MIXER_SSM:
        if site.mlp == "dense":
            k1, k2 = jax.random.split(key)
            return {"ssm": init_ssm(k1, ssm_opts(cfg), dtype),
                    "norm1": jnp.zeros((cfg.d_model,), dtype),
                    "norm2": jnp.zeros((cfg.d_model,), dtype),
                    "mlp": init_mlp(k2, cfg.d_model, site.d_ff, dtype)}
        k1, = jax.random.split(key, 1)
        return {"ssm": init_ssm(k1, ssm_opts(cfg), dtype),
                "norm1": jnp.zeros((cfg.d_model,), dtype)}
    if site.mixer == MIXER_SHARED_ATTN:
        return {}  # weights live in params["shared"]
    k1, k2 = jax.random.split(key)
    p = {"norm1": jnp.zeros((cfg.d_model,), dtype),
         "norm2": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.post_norm:
        p["norm1_post"] = jnp.zeros((cfg.d_model,), dtype)
        p["norm2_post"] = jnp.zeros((cfg.d_model,), dtype)
    if cfg.mla is not None:
        p["attn"] = init_mla(k1, cfg.d_model, mla_opts(cfg), dtype)
    else:
        p["attn"] = init_attention(k1, cfg.d_model, attn_opts(cfg, site), dtype)
    if site.mlp == "dense":
        p["mlp"] = init_mlp(k2, cfg.d_model, site.d_ff, dtype)
    elif site.mlp == "moe":
        p["moe"] = init_moe(k2, cfg.d_model, moe_opts(cfg), dtype)
    return p


def init_shared_block(cfg: ModelConfig, key, dtype):
    """Zamba2 shared attention+mlp block (one copy)."""
    site = LayerSite(MIXER_SHARED_ATTN, "dense", cfg.d_ff, cfg.rope_theta)
    k1, k2 = jax.random.split(key)
    return {
        "norm1": jnp.zeros((cfg.d_model,), dtype),
        "norm2": jnp.zeros((cfg.d_model,), dtype),
        "attn": init_attention(k1, cfg.d_model,
                               attn_opts(cfg, site), dtype),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _stack_init(fn, key, n: int):
    """Initialize n copies with different keys, stacked on axis 0."""
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def init_stage(cfg: ModelConfig, stage: Stage, key, dtype):
    if stage.kind == "run":
        site = stage.sites[0]
        return _stack_init(lambda k: _init_site(cfg, site, k, dtype), key,
                           stage.repeats)
    # pattern: tuple over period positions, each stacked over repeats
    keys = jax.random.split(key, len(stage.sites))
    return tuple(
        _stack_init(lambda k, s=s: _init_site(cfg, s, k, dtype), kk,
                    stage.repeats)
        for s, kk in zip(stage.sites, keys))


# ---------------------------------------------------------------------------
# Per-site caches
# ---------------------------------------------------------------------------

def _site_cache_len(site: LayerSite, max_len: int) -> int:
    if site.window:
        return min(site.window, max_len)
    return max_len


def _init_site_cache(cfg: ModelConfig, site: LayerSite, batch: int,
                     max_len: int, dtype):
    if site.mixer == MIXER_SSM:
        return init_ssm_cache(batch, ssm_opts(cfg), dtype)
    if cfg.mla is not None:
        return init_mla_cache(batch, _site_cache_len(site, max_len),
                              mla_opts(cfg), dtype)
    return init_kv_cache(batch, _site_cache_len(site, max_len),
                         attn_opts(cfg, site), dtype, quant=cfg.kv_quant)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Empty cache pytree mirroring the stage structure."""
    def stacked(site, n):
        one = _init_site_cache(cfg, site, batch, max_len, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)

    out = []
    for st in plan_stages(cfg):
        if st.kind == "run":
            out.append(stacked(st.sites[0], st.repeats))
        else:
            out.append(tuple(stacked(s, st.repeats) for s in st.sites))
    return tuple(out)


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int, dtype,
                     n_slots: int = 0):
    """Empty paged KV pool pytree mirroring the stage structure: every
    attention site gets (n_pages, page_size, kv, hd) pool tensors instead
    of per-sequence (batch, L) rows. One logical page allocates the same
    physical row in every layer's pool, so a single block table per
    sequence addresses the whole stack. Windowed sites share the layout
    (the decode mask enforces the window). Every SSM site gets a row of
    recurrent state per serving slot (``n_slots``): ``{"state" (n_slots,
    H, P, N) f32, "conv" (n_slots, d_conv - 1, channels)}``, beside the
    pages in the same tree. MLA archs have no paged form."""
    if cfg.mla is not None:
        raise ValueError("paged KV caches support attention-family models "
                         "(MLA latents are not paged)")
    if cfg.ssm is not None and n_slots < 1:
        raise ValueError("a paged cache with SSM sites needs n_slots")

    def stacked(site, n):
        if site.mixer == MIXER_SSM:
            one = init_ssm_cache(n_slots, ssm_opts(cfg), dtype)
        else:
            one = init_paged_kv_pool(n_pages, page_size,
                                     attn_opts(cfg, site), dtype,
                                     quant=cfg.kv_quant)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)

    out = []
    for st in plan_stages(cfg):
        if st.kind == "run":
            out.append(stacked(st.sites[0], st.repeats))
        else:
            out.append(tuple(stacked(s, st.repeats) for s in st.sites))
    return tuple(out)


def paged_sweep_layers(cfg: ModelConfig, pool, batch: int, n_cols: int):
    """``(window, chunk columns, layers)`` of each attention site of a
    paged ``pool`` (from ``init_paged_cache``): how its decode sweep
    chunks a (batch, n_cols) block table (``attn_decode_paged``)."""
    out = []
    for st, c in zip(plan_stages(cfg), pool):
        for site, leaf in zip(st.sites, (c,) if st.kind == "run" else c):
            if site.mixer == MIXER_SSM:
                continue
            n, _, ps, kv, hd = leaf["k"].shape
            out.append((site.window,
                        sweep_chunk_cols(batch, n_cols, ps, kv, hd,
                                         leaf["k"].dtype.itemsize), n))
    return out


# ---------------------------------------------------------------------------
# Site application
# ---------------------------------------------------------------------------

def _residual(cfg, y):
    """A branch's output as it joins the residual stream."""
    r = cfg.ssm.residual_multiplier if cfg.ssm is not None else 1.0
    return y if r == 1.0 else y * r


def _ssm_site(cfg, site, p, x, mixer):
    """An SSM site: ``mixer(h) -> (y, cache)`` on the normed input, then
    the site's MLP where it has one. Returns (x', cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    with jax.named_scope("rc3e.ssm"):
        y, cache = mixer(h)
    x = x + _residual(cfg, y)
    if site.mlp != "none":
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        y, aux = _mlp(cfg, site, p, h, x, aux)
        x = x + _residual(cfg, y)
    return x, cache, aux


def _mlp(cfg, site, pp, h, x, aux):
    """A site's feed-forward half: (y, aux)."""
    with jax.named_scope("rc3e.mlp"):
        if site.mlp == "dense":
            return mlp_forward(pp["mlp"], h, cfg.act), aux
        if site.mlp == "moe":
            return moe_forward(pp["moe"], h, moe_opts(cfg))
        return jnp.zeros_like(x), aux


def _apply_site_full(cfg, site, p, shared, x, positions, mode, max_len, dtype,
                     clamp_window: bool = True, valid=None):
    """Full-sequence site application.

    mode: "train" (no cache) | "prefill" (returns filled cache).
    ``clamp_window=False`` builds full-``max_len`` caches even for windowed
    sites (no ring) — the layout the paged splice expects. ``valid``
    (B, S) marks the real positions of padded rows: an SSM site's state
    and conv tail are taken at the real length.
    Returns (x', cache_or_None, aux).
    """
    aux = jnp.zeros((), jnp.float32)
    if site.mixer == MIXER_SSM:
        def mixer(h):
            y, (state, conv_tail) = ssm_forward(p["ssm"], h, ssm_opts(cfg),
                                                valid=valid)
            return y, {"state": state, "conv": conv_tail}
        x, cache, aux = _ssm_site(cfg, site, p, x, mixer)
        return x, cache if mode == "prefill" else None, aux

    pp = shared if site.mixer == MIXER_SHARED_ATTN else p
    h = rms_norm(x, pp["norm1"], cfg.norm_eps)
    with jax.named_scope("rc3e.attn"):
        if cfg.mla is not None:
            y, (c_kv, k_rope) = mla_forward(pp["attn"], h, positions,
                                            mla_opts(cfg))
        else:
            y, (k, v) = attn_forward(pp["attn"], h, positions,
                                     attn_opts(cfg, site))
    if cfg.post_norm:
        y = rms_norm(y, p["norm1_post"], cfg.norm_eps)
    x = x + _residual(cfg, y)

    h = rms_norm(x, pp["norm2"], cfg.norm_eps)
    y, aux = _mlp(cfg, site, pp, h, x, aux)
    if cfg.post_norm:
        y = rms_norm(y, p["norm2_post"], cfg.norm_eps)
    x = x + _residual(cfg, y)

    cache = None
    if mode == "prefill":
        L = _site_cache_len(site, max_len) if clamp_window else max_len
        if cfg.mla is not None:
            cache = fill_mla_cache(
                init_mla_cache(x.shape[0], L, mla_opts(cfg), dtype),
                c_kv, k_rope, positions)
        else:
            cache = fill_kv_cache(
                init_kv_cache(x.shape[0], L, attn_opts(cfg, site), dtype,
                              quant=cfg.kv_quant),
                k, v, positions)
    return x, cache, aux


def _apply_site_decode_paged(cfg, site, p, shared, x, positions, cache,
                             block_tables):
    """Decode one site against its paged pool (block-table indirection).
    An SSM site steps every slot's state row and keeps the old row where
    the slot is inactive (position -1)."""
    if site.mixer == MIXER_SSM:
        live = positions[:, 0] >= 0

        def mixer(h):
            y, new = ssm_decode(p["ssm"], h, cache, ssm_opts(cfg))
            return y, jax.tree.map(
                lambda n, o: jnp.where(
                    live.reshape((-1,) + (1,) * (o.ndim - 1)), n, o),
                new, cache)
        return _ssm_site(cfg, site, p, x, mixer)
    aux = jnp.zeros((), jnp.float32)
    pp = shared if site.mixer == MIXER_SHARED_ATTN else p
    h = rms_norm(x, pp["norm1"], cfg.norm_eps)
    with jax.named_scope("rc3e.attn"):
        y, cache = attn_decode_paged(pp["attn"], h, positions, cache,
                                     block_tables, attn_opts(cfg, site))
    if cfg.post_norm:
        y = rms_norm(y, p["norm1_post"], cfg.norm_eps)
    x = x + _residual(cfg, y)
    h = rms_norm(x, pp["norm2"], cfg.norm_eps)
    y, aux = _mlp(cfg, site, pp, h, x, aux)
    if cfg.post_norm:
        y = rms_norm(y, p["norm2_post"], cfg.norm_eps)
    return x + _residual(cfg, y), cache, aux


def _apply_site_decode(cfg, site, p, shared, x, positions, cache):
    if site.mixer == MIXER_SSM:
        return _ssm_site(cfg, site, p, x,
                         lambda h: ssm_decode(p["ssm"], h, cache,
                                              ssm_opts(cfg)))
    aux = jnp.zeros((), jnp.float32)

    pp = shared if site.mixer == MIXER_SHARED_ATTN else p
    h = rms_norm(x, pp["norm1"], cfg.norm_eps)
    with jax.named_scope("rc3e.attn"):
        if cfg.mla is not None:
            y, cache = mla_decode(pp["attn"], h, positions, cache,
                                  mla_opts(cfg))
        else:
            y, cache = attn_decode(pp["attn"], h, positions, cache,
                                   attn_opts(cfg, site))
    if cfg.post_norm:
        y = rms_norm(y, p["norm1_post"], cfg.norm_eps)
    x = x + _residual(cfg, y)
    h = rms_norm(x, pp["norm2"], cfg.norm_eps)
    y, aux = _mlp(cfg, site, pp, h, x, aux)
    if cfg.post_norm:
        y = rms_norm(y, p["norm2_post"], cfg.norm_eps)
    return x + _residual(cfg, y), cache, aux


# ---------------------------------------------------------------------------
# Stage execution
# ---------------------------------------------------------------------------

def _seq_shard(x):
    """Sequence parallelism for remat residuals (Megatron-SP): constrain the
    carried (B, S, d) activation to (dp, "model", None) so the per-layer
    residual stack saved by checkpoint is sharded over the TP axis too —
    without this the stack is (L, B/dp, S, d) bf16 per device (12.9 GB on
    qwen3 train_4k), with it L·B·S·d/(dp·tp). No-op without a mesh.

    Applied at the END of each scan body (the loop-carry boundary): the
    saved residual is the body *input*, so only the carry needs the small
    sharding; compute inside the body runs on gathered activations."""
    from jax.sharding import PartitionSpec as P
    for dp in (("pod", "data"), "data", None):
        try:
            return jax.lax.with_sharding_constraint(x, P(dp, "model", None))
        except Exception:  # noqa: BLE001 - axis not in ambient mesh
            continue
    return x


def _gather_act(x):
    """Applied at the START of each scan body: re-gather the seq dim so the
    layer's dots see (dp, None, None) activations against model-sharded
    weights. Without this GSPMD resolves the axis conflict by all-gathering
    the WEIGHTS instead — measured 3.9 TB/device per step on llava-34B
    train_4k (f32 weight gathers ×60 layers in fwd+bwd loops)."""
    from jax.sharding import PartitionSpec as P
    for dp in (("pod", "data"), "data", None):
        try:
            return jax.lax.with_sharding_constraint(x, P(dp, None, None))
        except Exception:  # noqa: BLE001 - axis not in ambient mesh
            continue
    return x


def apply_stages(cfg: ModelConfig, params, x, positions, *,
                 mode: str = "train", caches=None, max_len: int = 0,
                 remat: bool = False, cache_dtype=None, block_tables=None,
                 clamp_window: bool = True, valid=None):
    """Run all stages. mode: train | prefill | decode.

    ``block_tables`` (B, nb) switches decode to the paged-pool path (caches
    must come from ``init_paged_cache``). ``clamp_window=False`` makes
    prefill build full-length caches for windowed sites (paged splice
    layout). ``valid`` (B, S): the real positions of a padded prefill.
    Returns (x, new_caches_or_None, aux_sum).
    """
    stages = plan_stages(cfg)
    shared = params.get("shared")
    dtype = cache_dtype or x.dtype
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    # Megatron-SP constraints only make sense with a TP axis in play
    use_sp = remat and cfg.tp_mode == "tp"

    def decode_site(site, p_i, c_i, xx):
        if block_tables is not None:
            return _apply_site_decode_paged(cfg, site, p_i, shared, xx,
                                            positions, c_i, block_tables)
        return _apply_site_decode(cfg, site, p_i, shared, xx, positions, c_i)

    for si, st in enumerate(stages):
        sp = params["stages"][si]
        sc = caches[si] if caches is not None else None

        if st.kind == "run":
            site = st.sites[0]
            if mode == "decode":
                def body(carry, xs, site=site):
                    xx, aux = carry
                    p_i, c_i = xs
                    xx, c_i, a = decode_site(site, p_i, c_i, xx)
                    return (xx, aux + a), c_i
            else:
                def body(carry, p_i, site=site):
                    xx, aux = carry
                    if use_sp:
                        xx = _gather_act(xx)
                    xx, c_i, a = _apply_site_full(cfg, site, p_i, shared, xx,
                                                  positions, mode, max_len,
                                                  dtype, clamp_window, valid)
                    if use_sp:
                        xx = _seq_shard(xx)
                    return (xx, aux + a), c_i
            if remat:
                body = jax.checkpoint(body)
            xs = (sp, sc) if mode == "decode" else sp
            (x, aux_total), ys = jax.lax.scan(
                body, (x, aux_total), xs)
            new_caches.append(ys)
        else:  # pattern
            sites = st.sites
            if mode == "decode":
                def body(carry, xs, sites=sites):
                    xx, aux = carry
                    ps, cs = xs
                    outc = []
                    for site_i, (p_i, c_i) in zip(sites, zip(ps, cs)):
                        xx, c_i, a = decode_site(site_i, p_i, c_i, xx)
                        aux = aux + a
                        outc.append(c_i)
                    return (xx, aux), tuple(outc)
            else:
                def body(carry, ps, sites=sites):
                    xx, aux = carry
                    if use_sp:
                        xx = _gather_act(xx)
                    outc = []
                    for site_i, p_i in zip(sites, ps):
                        xx, c_i, a = _apply_site_full(
                            cfg, site_i, p_i, shared, xx, positions, mode,
                            max_len, dtype, clamp_window, valid)
                        aux = aux + a
                        outc.append(c_i)
                    if use_sp:
                        xx = _seq_shard(xx)
                    return (xx, aux), tuple(outc)
            if remat:
                body = jax.checkpoint(body)
            xs = (sp, sc) if mode == "decode" else sp
            (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), xs)
            new_caches.append(ys)

    out_caches = tuple(new_caches) if mode in ("prefill", "decode") else None
    return x, out_caches, aux_total
