"""Plain float32 reference forward pass for dense decoder-only LMs with
global (GQA) attention — llama/smollm-style blocks.

Written straight in ``jax.numpy``: one layer after another, the whole
causal score matrix, no Pallas kernels, no KV cache, no batching, no layer
scan, at ``jax.default_matmul_precision("highest")`` so a TPU computes the
float32 matmuls in float32. It reads parameters in this repository's
layout. Serving logits (prefill, then decode through the cache) are
compared with it.

Departures from the published SmolLM description, shared with the system:
RMSNorm weights are stored as ``w - 1`` (applied as ``1 + w``), and the
norm epsilon is ``cfg.norm_eps``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN_GLOBAL, ModelConfig


def _unsupported(cfg: ModelConfig) -> list:
    checks = {
        "family": cfg.family != "dense",
        "moe": cfg.moe is not None,
        "mla": cfg.mla is not None,
        "ssm": cfg.ssm is not None,
        "pattern": any(k != ATTN_GLOBAL for k in cfg.pattern),
        "softcap": bool(cfg.attn_softcap or cfg.final_softcap),
        "qk_norm": cfg.qk_norm,
        "post_norm": cfg.post_norm,
        "embed_scale": cfg.embed_scale,
        "patches": bool(cfg.n_patches),
        "non_causal": not cfg.causal,
        "no_rope": not cfg.use_rope,
    }
    return [name for name, bad in checks.items() if bad]


def _layers(params):
    """Per-layer parameter dicts, in order, from the stacked stages."""
    out = []
    for stage in params["stages"]:
        parts = stage if isinstance(stage, tuple) else (stage,)
        repeats = jax.tree.leaves(parts[0])[0].shape[0]
        for r in range(repeats):
            for part in parts:
                out.append(jax.tree.map(lambda a: a[r], part))
    return out


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    """x (S, heads, hd): rotate the two halves of each head by position."""
    S, _, hd = x.shape
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(name: str, x):
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    return jax.nn.gelu(x, approximate=True)


def _layer(cfg: ModelConfig, p, x):
    S, d = x.shape
    hd = cfg.resolved_head_dim
    Hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    f32 = lambda a: a.astype(jnp.float32)        # noqa: E731
    h = _rms_norm(x, f32(p["norm1"]), cfg.norm_eps)
    a = p["attn"]
    q = (h @ f32(a["wq"]).reshape(d, -1)).reshape(S, Hkv * g, hd)
    k = (h @ f32(a["wk"]).reshape(d, -1)).reshape(S, Hkv, hd)
    v = (h @ f32(a["wv"]).reshape(d, -1)).reshape(S, Hkv, hd)
    q = _rope(q, cfg.rope_theta).reshape(S, Hkv, g, hd)
    k = _rope(k, cfg.rope_theta)
    scale = cfg.query_scale or hd ** -0.5
    scores = jnp.einsum("qhgc,khc->hgqk", q * scale, k)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqk,khc->qhgc", probs, v).reshape(S, -1)
    x = x + o @ f32(a["wo"]).reshape(-1, d)
    h = _rms_norm(x, f32(p["norm2"]), cfg.norm_eps)
    m = p["mlp"]
    y = _act(cfg.act, h @ f32(m["wg"])) * (h @ f32(m["wu"]))
    return x + y @ f32(m["wd"])


def reference_logits(cfg: ModelConfig, params, tokens):
    """tokens (S,) int -> float32 logits (S, vocab): the logits at row i
    predict token i + 1."""
    bad = _unsupported(cfg)
    if bad:
        raise NotImplementedError(f"reference forward does not cover "
                                  f"{cfg.name}: {bad}")
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["tok"].astype(jnp.float32)
        x = emb[jnp.asarray(tokens)]
        for p in _layers(params):
            x = _layer(cfg, p, x)
        h = _rms_norm(x, params["final_norm"].astype(jnp.float32),
                      cfg.norm_eps)
        head = emb.T if cfg.tie_embeddings \
            else params["head"].astype(jnp.float32)
        return h @ head
