"""Unified decoder-only LM covering dense / MoE / SSM / hybrid / VLM families.

Public surface (all pure functions):
  init_lm(cfg, key)                         -> params
  lm_forward(cfg, params, tokens, ...)      -> (hidden, aux)        [train]
  lm_logits(cfg, params, hidden)            -> logits
  lm_prefill(cfg, params, tokens, max_len)  -> (hidden, caches)
  lm_decode(cfg, params, caches, tok, pos)  -> (logits, caches)

VLM (llava): `patches` (B, P, d_model) precomputed patch embeddings (stub
frontend per assignment) are prepended to the token embeddings; `tokens` then
has S - P entries so the combined length equals the cell's seq_len.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MIXER_SHARED_ATTN, ModelConfig
from repro.layers.embeddings import embed, init_embedding
from repro.layers.norms import rms_norm, softcap
from repro.models.stages import (apply_stages, init_cache, init_paged_cache,
                                 init_shared_block, init_stage, plan_stages)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def init_lm(cfg: ModelConfig, key) -> dict:
    pdt = _param_dtype(cfg)
    stages = plan_stages(cfg)
    keys = jax.random.split(key, len(stages) + 3)
    params = {
        "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, pdt),
        "final_norm": jnp.zeros((cfg.d_model,), pdt),
        "stages": tuple(init_stage(cfg, st, keys[3 + i], pdt)
                        for i, st in enumerate(stages)),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_size), pdt) * cfg.d_model ** -0.5)
    if any(s.mixer == MIXER_SHARED_ATTN for st in stages for s in st.sites):
        params["shared"] = init_shared_block(cfg, keys[2], pdt)
    return params


def _embed_tokens(cfg, params, tokens, patches=None):
    with jax.named_scope("rc3e.embed"):
        x = embed(params["embed"], tokens, scale_by_dim=cfg.embed_scale)
        x = x.astype(_dtype(cfg))
        if cfg.ssm is not None and cfg.ssm.embedding_multiplier != 1.0:
            x = x * cfg.ssm.embedding_multiplier
        if patches is not None:
            x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    return x


def _positions(x):
    B, S = x.shape[:2]
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))


def lm_forward(cfg: ModelConfig, params, tokens, patches=None,
               remat: bool = False):
    """Teacher-forced full-sequence forward. Returns (hidden, aux_loss)."""
    x = _embed_tokens(cfg, params, tokens, patches)
    pos = _positions(x)
    x, _, aux = apply_stages(cfg, params, x, pos, mode="train", remat=remat)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return h, aux


def lm_logits(cfg: ModelConfig, params, h):
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]
    out = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype))
    if cfg.ssm is not None and cfg.ssm.logits_scaling != 1.0:
        out = out / cfg.ssm.logits_scaling
    return softcap(out, cfg.final_softcap)


def lm_prefill(cfg: ModelConfig, params, tokens, max_len: int, patches=None,
               clamp_window: bool = True):
    """Run the prompt, building decode caches sized ``max_len``.

    ``clamp_window=False`` builds full-length (non-ring) caches even for
    windowed sites — the layout the paged page-splice expects. With SSM
    sites a row may end in padding, marked by negative token ids: the
    recurrent state and conv tail are taken at the real length (the
    padding embeds as token 0)."""
    valid = None
    if cfg.ssm is not None:
        valid = tokens >= 0
        tokens = jnp.maximum(tokens, 0)
    x = _embed_tokens(cfg, params, tokens, patches)
    pos = _positions(x)
    x, caches, _ = apply_stages(cfg, params, x, pos, mode="prefill",
                                max_len=max_len, cache_dtype=_dtype(cfg),
                                clamp_window=clamp_window, valid=valid)
    with jax.named_scope("rc3e.head"):
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return h, caches


def _head(cfg: ModelConfig, params, x):
    """Final norm and logits of a decode step."""
    with jax.named_scope("rc3e.head"):
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return lm_logits(cfg, params, h)


def lm_decode(cfg: ModelConfig, params, caches, tokens, pos):
    """One decode step. tokens (B,1) int32, pos (B,) absolute positions."""
    x = _embed_tokens(cfg, params, tokens)
    positions = pos[:, None].astype(jnp.int32)
    x, caches, _ = apply_stages(cfg, params, x, positions, mode="decode",
                                caches=caches)
    return _head(cfg, params, x), caches


def lm_decode_paged(cfg: ModelConfig, params, caches, tokens, pos,
                    block_tables):
    """One decode step against the paged KV pool. tokens (B,1) int32;
    pos (B,) absolute positions (-1 = inactive row); block_tables (B, nb)
    int32 page ids."""
    x = _embed_tokens(cfg, params, tokens)
    positions = pos[:, None].astype(jnp.int32)
    x, caches, _ = apply_stages(cfg, params, x, positions, mode="decode",
                                caches=caches, block_tables=block_tables)
    return _head(cfg, params, x), caches


def make_decode_caches(cfg: ModelConfig, batch: int, max_len: int):
    """Empty caches (for dry-run input specs and serving allocation)."""
    return init_cache(cfg, batch, max_len, _dtype(cfg))


def make_paged_caches(cfg: ModelConfig, n_pages: int, page_size: int,
                      n_slots: int = 0):
    """Empty paged KV pool (shared across every serving slot), with a
    recurrent-state row per slot for each SSM site."""
    return init_paged_cache(cfg, n_pages, page_size, _dtype(cfg), n_slots)
