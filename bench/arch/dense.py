"""A dense decoder-only LM with grouped-query attention (global, or a
sliding window of ``Dims.window`` positions in every layer: a query
attends the keys less than the window behind it), RoPE and a SiLU-gated
MLP.

Its sizes as the source publishes them, the program's ``ModelConfig``,
the weight layout, the float32 reference layer and logits, and the bytes
and operations of a decode step and a prefill, computed from the shapes.
The reference is written straight in ``jax.numpy``: the whole masked
score matrix, no cache, no kernels, float32 at ``Precision.HIGHEST``.
RMSNorm weights are stored as ``w - 1`` and applied as ``1 + w``; RoPE
rotates the two halves of each head.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from reference import HI, _mm, _rms_norm, _rope
from weights import Layout


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm_eps: float
    rope_theta: float
    max_position: int
    window: int = 0             # sliding-window width; 0 = global attention

    def attended(self, ctx: int) -> int:
        """Positions a query at position ``ctx - 1`` attends."""
        return min(ctx, self.window) if self.window else ctx

    def attended_prefill(self, S: int) -> int:
        """Query-key pairs a causal prefill of ``S`` positions scores."""
        w = self.window if self.window and self.window < S else S
        return w * (w + 1) // 2 + (S - w) * w

    # parameter counts (the yardstick's own, from the shapes)
    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def weight_bytes_per_step(self) -> int:
        """Bytes of bfloat16 weights one decode step reads: every layer's
        matrices and norms, the final norm and the output head. The
        embedding table is gathered a row per token, not read whole."""
        per_layer = self.layer_matmul_params + 2 * self.d_model
        return 2 * (self.n_layers * per_layer + self.d_model
                    + self.head_params)

    @property
    def kv_bytes_per_position(self) -> int:
        """bfloat16 K and V plus the int32 position tag, all layers."""
        return self.n_layers * (2 * 2 * self.n_kv_heads * self.head_dim + 4)


def sizes(name: str, conf: dict) -> Dims:
    """The sizes of a configuration file's ``model`` block, under the keys
    its source publishes them."""
    if conf["hidden_act"] != "silu":
        raise ValueError(f"{name}: only silu MLPs are covered")
    heads = conf["num_attention_heads"]
    return Dims(
        name=name, n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=heads,
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        tied=conf["tie_word_embeddings"], norm_eps=conf["rms_norm_eps"],
        rope_theta=conf["rope_theta"],
        max_position=conf["max_position_embeddings"],
        window=conf.get("sliding_window") or 0)


def program(m: Dims):
    """The program's ``ModelConfig`` for these sizes, served in bfloat16:
    every layer windowed where the source states a sliding window."""
    from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL, ModelConfig
    local = dict(pattern=(ATTN_LOCAL,), window=m.window) if m.window \
        else dict(pattern=(ATTN_GLOBAL,))
    return ModelConfig(
        name=m.name, family="dense", n_layers=m.n_layers,
        d_model=m.d_model, n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
        head_dim=m.head_dim, d_ff=m.d_ff, vocab_size=m.vocab,
        rope_theta=m.rope_theta, **local,
        tie_embeddings=m.tied, max_seq_len=m.max_position,
        norm_eps=m.norm_eps, act="silu", dtype="bfloat16",
        param_dtype="bfloat16")


def layout(m: Dims) -> Layout:
    """One "run" stage: every layer's leaves stacked under ``stages/0/``."""
    d, L, F, V = m.d_model, m.n_layers, m.d_ff, m.vocab
    kv, g, hd = m.n_kv_heads, m.n_heads // m.n_kv_heads, m.head_dim
    out = {
        "embed/tok": ((V, d), d ** -0.5, False),
        "final_norm": ((d,), 0.1, False),
        "stages/0/norm1": ((L, d), 0.1, True),
        "stages/0/norm2": ((L, d), 0.1, True),
        "stages/0/attn/wq": ((L, d, kv, g, hd), d ** -0.5, True),
        "stages/0/attn/wk": ((L, d, kv, hd), d ** -0.5, True),
        "stages/0/attn/wv": ((L, d, kv, hd), d ** -0.5, True),
        "stages/0/attn/wo": ((L, kv, g, hd, d), (kv * g * hd) ** -0.5, True),
        "stages/0/mlp/wg": ((L, d, F), d ** -0.5, True),
        "stages/0/mlp/wu": ((L, d, F), d ** -0.5, True),
        "stages/0/mlp/wd": ((L, F, d), F ** -0.5, True),
    }
    if not m.tied:
        out["head"] = ((d, V), d ** -0.5, False)
    return out


def layer_at(m: Dims, r: int):
    """Layer ``r`` is entry ``r`` of the one stage's stacked leaves."""
    return "stages/0/", r


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(m: Dims, p, x, quant):
    S, d = x.shape
    hd, kv, g = m.head_dim, m.n_kv_heads, m.n_heads // m.n_kv_heads
    h = _rms_norm(x, p["norm1"], m.norm_eps)
    q = _mm(h, p["attn/wq"].reshape(d, -1), quant).reshape(S, kv * g, hd)
    k = _mm(h, p["attn/wk"].reshape(d, -1), quant).reshape(S, kv, hd)
    v = _mm(h, p["attn/wv"].reshape(d, -1), quant).reshape(S, kv, hd)
    q = _rope(q, m.rope_theta).reshape(S, kv, g, hd)
    k = _rope(k, m.rope_theta)
    scores = jnp.einsum("qhgc,khc->hgqk", q * hd ** -0.5, k, precision=HI)
    diff = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    allowed = (diff >= 0) & ((diff < m.window) if m.window else True)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqk,khc->qhgc", probs, v, precision=HI).reshape(S, -1)
    x = x + _mm(o, p["attn/wo"].reshape(-1, d), quant)
    h = _rms_norm(x, p["norm2"], m.norm_eps)
    y = jax.nn.silu(_mm(h, p["mlp/wg"], quant)) * _mm(h, p["mlp/wu"], quant)
    return x + _mm(y, p["mlp/wd"], quant)


def layer(m: Dims, r: int, p, x, quant):
    """Reference layer ``r`` over ``x`` (S, d_model); every layer is
    alike."""
    return _layer(m, p, x, quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(m: Dims, top, h, quant):
    h = _rms_norm(h, top["final_norm"], m.norm_eps)
    head = top["embed/tok"].T if m.tied else top["head"]
    return _mm(h, head, quant)


def embed(m: Dims, top, toks):
    return top["embed/tok"][toks]


def decode_work(m: Dims, ctxs):
    """(bytes, flops) of one decode execution over rows at contexts
    ``ctxs``: reading the weights once, the live KV of each row (the
    window's, where the model has one) and writing one position per
    row."""
    kv_pos, flops = 0, 0.0
    per_token = 2.0 * (m.n_layers * m.layer_matmul_params + m.head_params)
    attn = 4.0 * m.n_layers * m.n_heads * m.head_dim
    for c in ctxs:
        ctx = m.attended(c)
        kv_pos += ctx + 1
        flops += per_token + attn * ctx
    return m.weight_bytes_per_step + kv_pos * m.kv_bytes_per_position, flops


def prefill_flops(m: Dims, S: int) -> float:
    """Operations of a causal prefill of ``S`` positions: 2 per weight per
    token, plus the scores and values of the pairs the mask keeps."""
    return 2.0 * m.n_layers * m.layer_matmul_params * S \
        + 4.0 * m.n_layers * m.n_heads * m.head_dim * m.attended_prefill(S)
