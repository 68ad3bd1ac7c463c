"""A hybrid decoder-only LM after IBM's GraniteMoeHybrid with no experts
(granite-4.0-h): Mamba-2 layers beside grouped-query attention layers in
the order ``layer_types`` publishes, a SwiGLU MLP after every layer,
tied embeddings, and three scalars (the embedding multiplier, the
residual multiplier on both branches of every layer, the logits divisor).

Per layer, as HF ``GraniteMoeHybridDecoderLayer`` computes it:

  h   = x + r * mixer(rms(x))
  out = h + r * mlp(rms(h))

The attention mixer uses no positional encoding and scales its scores by
``attention_multiplier``. The Mamba-2 mixer projects ``[z, xBC, dt]``,
runs a causal depthwise conv with bias and SiLU over ``xBC``, splits it
into ``x, B, C``, sets ``dt = softplus(dt + dt_bias)`` and ``A =
-exp(A_log)``, runs the recurrence

  s_t = exp(dt_t A) s_{t-1} + dt_t x_t (x) B_t,   y_t = C_t . s_t + D x_t

and returns ``out_proj(rms(y * silu(z)) * w)``. The logits are
``embed^T rms(h_L) / logits_scaling``.

The reference is written straight in ``jax.numpy``: the whole masked
score matrix for attention, and the recurrence as a sequential scan over
positions (not the chunked form the program runs), float32 at
``Precision.HIGHEST``, every weight matmul through ``reference._mm`` so
that the float8 control rounds the same operands here as for the other
architectures.

Departures from HF: RMSNorm weights, the gated one included, are stored
as ``w - 1`` and applied as ``1 + w`` (the benchmark draws mean-zero
weights); ``time_step_limit`` is HF's default (0, inf), so no clamp is
written; the Mamba-2 layers are computed with one group (``n_groups`` 1,
as published) and refuse another.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from reference import HI, _mm, _rms_norm
from weights import Layout

MAMBA, ATTENTION = "mamba", "attention"

# scales of the mean-zero draws of the Mamba-2 scalars per head: wide
# enough that some heads keep a memory of hundreds of positions (a small
# dt * |A|) while others forget within a few (see the configuration's
# "assumed")
A_LOG_SCALE = 3.0
DT_BIAS_SCALE = 2.5
# the tied embedding's draw, in units of d ** -0.5. At unit scale the
# embedding multiplier makes a token's own row outweigh all the layers
# add, and the random model serves its input token back whatever its
# layers computed; at a tenth the layers decide the next token.
EMBED_SCALE = 0.1


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    n_layers: int
    layer_types: tuple          # MAMBA / ATTENTION per layer
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm_eps: float
    max_position: int
    attn_scale: float
    embed_mult: float
    residual_mult: float
    logits_scale: float
    d_state: int
    d_conv: int
    expand: int
    ssm_heads: int
    ssm_head_dim: int
    chunk: int

    @property
    def period(self) -> tuple:
        """The shortest prefix of ``layer_types`` that tiles it."""
        t = self.layer_types
        for p in range(1, len(t) + 1):
            if len(t) % p == 0 and t == t[:p] * (len(t) // p):
                return t[:p]
        return t

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.d_state

    @property
    def proj_out(self) -> int:
        return self.d_inner + self.conv_channels + self.ssm_heads

    @property
    def n_attn(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count(MAMBA)

    # parameter counts (the yardstick's own, from the shapes)
    @property
    def mlp_params(self) -> int:
        return 3 * self.d_model * self.d_ff

    @property
    def attn_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)

    @property
    def mamba_matmul_params(self) -> int:
        return self.d_model * self.proj_out + self.d_inner * self.d_model

    @property
    def layers_matmul_params(self) -> int:
        return (self.n_layers * self.mlp_params
                + self.n_attn * self.attn_matmul_params
                + self.n_mamba * self.mamba_matmul_params)

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def weight_bytes_per_step(self) -> int:
        """Bytes of bfloat16 weights one decode step reads: every layer's
        matrices, norms, conv and per-head scalars, the final norm and the
        output head (the tied embedding, read whole). The embedding is
        otherwise gathered a row per token, not read whole."""
        d = self.d_model
        mamba_small = (self.d_conv * self.conv_channels + self.conv_channels
                       + 3 * self.ssm_heads + self.d_inner)
        per_layer = self.n_layers * 2 * d \
            + self.n_mamba * mamba_small
        return 2 * (self.layers_matmul_params + per_layer + d
                    + self.head_params)

    @property
    def kv_bytes_per_position(self) -> int:
        """bfloat16 K and V plus the int32 position tag, attention layers."""
        return self.n_attn * (2 * 2 * self.n_kv_heads * self.head_dim + 4)

    @property
    def state_bytes_per_row(self) -> int:
        """One slot's recurrent state over the Mamba-2 layers: the f32 SSD
        state and the bfloat16 conv tail."""
        state = 4 * self.ssm_heads * self.ssm_head_dim * self.d_state
        conv = 2 * (self.d_conv - 1) * self.conv_channels
        return self.n_mamba * (state + conv)


def sizes(name: str, conf: dict) -> Dims:
    """The sizes of a configuration file's ``model`` block, under the keys
    its source publishes them."""
    checks = {"hidden_act": "silu", "position_embedding_type": "nope",
              "num_local_experts": 0, "mamba_n_groups": 1,
              "mamba_proj_bias": False, "mamba_conv_bias": True,
              "attention_bias": False, "normalization_function": "rmsnorm"}
    for key, want in checks.items():
        if conf[key] != want:
            raise ValueError(f"{name}: only {key}={want!r} is covered")
    types = tuple(conf["layer_types"])
    if set(types) - {MAMBA, ATTENTION} or \
            len(types) != conf["num_hidden_layers"]:
        raise ValueError(f"{name}: layer_types must name mamba or "
                         "attention for each layer")
    heads = conf["num_attention_heads"]
    if conf["mamba_expand"] * conf["hidden_size"] != \
            conf["mamba_n_heads"] * conf["mamba_d_head"]:
        raise ValueError(f"{name}: mamba heads do not tile the inner width")
    return Dims(
        name=name, n_layers=conf["num_hidden_layers"], layer_types=types,
        d_model=conf["hidden_size"], n_heads=heads,
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // heads,
        d_ff=conf["shared_intermediate_size"], vocab=conf["vocab_size"],
        tied=conf["tie_word_embeddings"], norm_eps=conf["rms_norm_eps"],
        max_position=conf["max_position_embeddings"],
        attn_scale=conf["attention_multiplier"],
        embed_mult=conf["embedding_multiplier"],
        residual_mult=conf["residual_multiplier"],
        logits_scale=conf["logits_scaling"],
        d_state=conf["mamba_d_state"], d_conv=conf["mamba_d_conv"],
        expand=conf["mamba_expand"], ssm_heads=conf["mamba_n_heads"],
        ssm_head_dim=conf["mamba_d_head"], chunk=conf["mamba_chunk_size"])


def program(m: Dims):
    """The program's ``ModelConfig`` for these sizes, served in bfloat16:
    one pattern stage of the period, repeated."""
    from repro.configs.base import (ATTN_GLOBAL, MIXER_SSM, ModelConfig,
                                    SSMConfig)
    kinds = {MAMBA: MIXER_SSM, ATTENTION: ATTN_GLOBAL}
    return ModelConfig(
        name=m.name, family="hybrid", n_layers=m.n_layers,
        d_model=m.d_model, n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
        head_dim=m.head_dim, d_ff=m.d_ff, vocab_size=m.vocab,
        pattern=tuple(kinds[t] for t in m.period), use_rope=False,
        query_scale=m.attn_scale,
        ssm=SSMConfig(d_state=m.d_state, d_conv=m.d_conv, expand=m.expand,
                      head_dim=m.ssm_head_dim, n_groups=1, chunk=m.chunk,
                      site_mlp=True, embedding_multiplier=m.embed_mult,
                      residual_multiplier=m.residual_mult,
                      logits_scaling=m.logits_scale),
        tie_embeddings=m.tied, max_seq_len=m.max_position,
        norm_eps=m.norm_eps, act="silu", dtype="bfloat16",
        param_dtype="bfloat16")


def layout(m: Dims) -> Layout:
    """One pattern stage: the leaves of period position ``i`` stacked over
    the repeats under ``stages/0/<i>/`` (``layer_at``)."""
    d, F, V, R = m.d_model, m.d_ff, m.vocab, m.n_layers // len(m.period)
    kv, g, hd = m.n_kv_heads, m.n_heads // m.n_kv_heads, m.head_dim
    H, di, C = m.ssm_heads, m.d_inner, m.conv_channels
    out = {"embed/tok": ((V, d), EMBED_SCALE * d ** -0.5, False),
           "final_norm": ((d,), 0.1, False)}
    for i, kind in enumerate(m.period):
        s = layer_at(m, i)[0]
        out.update({
            s + "norm1": ((R, d), 0.1, True),
            s + "norm2": ((R, d), 0.1, True),
            s + "mlp/wg": ((R, d, F), d ** -0.5, True),
            s + "mlp/wu": ((R, d, F), d ** -0.5, True),
            s + "mlp/wd": ((R, F, d), F ** -0.5, True)})
        if kind == ATTENTION:
            out.update({
                s + "attn/wq": ((R, d, kv, g, hd), d ** -0.5, True),
                s + "attn/wk": ((R, d, kv, hd), d ** -0.5, True),
                s + "attn/wv": ((R, d, kv, hd), d ** -0.5, True),
                s + "attn/wo": ((R, kv, g, hd, d), (kv * g * hd) ** -0.5,
                                True)})
        else:
            out.update({
                s + "ssm/in_proj": ((R, d, m.proj_out), d ** -0.5, True),
                s + "ssm/conv_w": ((R, m.d_conv, C), m.d_conv ** -0.5,
                                   True),
                s + "ssm/conv_b": ((R, C), 0.1, True),
                s + "ssm/dt_bias": ((R, H), DT_BIAS_SCALE, True),
                s + "ssm/A_log": ((R, H), A_LOG_SCALE, True),
                s + "ssm/D": ((R, H), 1.0, True),
                s + "ssm/norm": ((R, di), 0.1, True),
                s + "ssm/out_proj": ((R, di, d), di ** -0.5, True)})
    return out


def layer_at(m: Dims, r: int):
    """Layer ``r`` is entry ``r // period`` of position ``r % period`` (of
    the one run stage, where the period is one layer)."""
    P = len(m.period)
    return ("stages/0/" if P == 1 else f"stages/0/{r % P}/"), r // P


def _mlp(m: Dims, p, x, quant):
    h = _rms_norm(x, p["norm2"], m.norm_eps)
    y = jax.nn.silu(_mm(h, p["mlp/wg"], quant)) * _mm(h, p["mlp/wu"], quant)
    return x + m.residual_mult * _mm(y, p["mlp/wd"], quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _attention_layer(m: Dims, p, x, quant):
    S, d = x.shape
    hd, kv, g = m.head_dim, m.n_kv_heads, m.n_heads // m.n_kv_heads
    h = _rms_norm(x, p["norm1"], m.norm_eps)
    q = _mm(h, p["attn/wq"].reshape(d, -1), quant).reshape(S, kv, g, hd)
    k = _mm(h, p["attn/wk"].reshape(d, -1), quant).reshape(S, kv, hd)
    v = _mm(h, p["attn/wv"].reshape(d, -1), quant).reshape(S, kv, hd)
    scores = jnp.einsum("qhgc,khc->hgqk", q * m.attn_scale, k, precision=HI)
    allowed = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqk,khc->qhgc", probs, v, precision=HI).reshape(S, -1)
    x = x + m.residual_mult * _mm(o, p["attn/wo"].reshape(-1, d), quant)
    return _mlp(m, p, x, quant)


def _recurrence(xs, dt, A, Bm, Cm, D):
    """The Mamba-2 recurrence, one position after another. xs (S, H, P),
    dt (S, H), A and D (H,), Bm and Cm (S, N) -> y (S, H, P)."""
    H, P = xs.shape[1:]

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * A)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        y = jnp.einsum("hpn,n->hp", s, c_t, precision=HI) \
            + D[:, None] * x_t
        return s, y

    s0 = jnp.zeros((H, P, Bm.shape[1]), jnp.float32)
    return jax.lax.scan(step, s0, (xs, dt, Bm, Cm), unroll=8)[1]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _mamba_layer(m: Dims, p, x, quant):
    S, _ = x.shape
    di, N, K = m.d_inner, m.d_state, m.d_conv
    h = _rms_norm(x, p["norm1"], m.norm_eps)
    zxbcdt = _mm(h, p["ssm/in_proj"], quant)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:di + m.conv_channels]
    dt = zxbcdt[:, di + m.conv_channels:]
    # causal depthwise conv: tap k reads the input K - 1 - k positions back
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(xp[k:k + S] * p["ssm/conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + p["ssm/conv_b"])
    xs = xbc[:, :di].reshape(S, m.ssm_heads, m.ssm_head_dim)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["ssm/dt_bias"])
    A = -jnp.exp(p["ssm/A_log"])
    y = _recurrence(xs, dt, A, Bm, Cm, p["ssm/D"]).reshape(S, di)
    y = _rms_norm(y * jax.nn.silu(z), p["ssm/norm"], m.norm_eps)
    x = x + m.residual_mult * _mm(y, p["ssm/out_proj"], quant)
    return _mlp(m, p, x, quant)


def layer(m: Dims, r: int, p, x, quant):
    """Reference layer ``r`` over ``x`` (S, d_model)."""
    if m.layer_types[r] == ATTENTION:
        return _attention_layer(m, p, x, quant)
    return _mamba_layer(m, p, x, quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(m: Dims, top, h, quant):
    h = _rms_norm(h, top["final_norm"], m.norm_eps)
    head = top["embed/tok"].T if m.tied else top["head"]
    return _mm(h, head, quant) / m.logits_scale


def embed(m: Dims, top, toks):
    return top["embed/tok"][toks] * m.embed_mult


def _mamba_decode_flops(m: Dims) -> float:
    """One position of one Mamba-2 layer outside its matmuls: the conv,
    the state's decay, update and read-out."""
    return 2.0 * m.d_conv * m.conv_channels \
        + 5.0 * m.ssm_heads * m.ssm_head_dim * m.d_state


def decode_work(m: Dims, ctxs):
    """(bytes, flops) of one decode execution over rows at contexts
    ``ctxs``: reading the weights once, the live KV of each row in the
    attention layers and writing one position per row, and reading and
    writing each row's recurrent state (the f32 SSD state and the conv
    tail) in every Mamba-2 layer."""
    kv_pos, flops = 0, 0.0
    per_token = 2.0 * (m.layers_matmul_params + m.head_params) \
        + m.n_mamba * _mamba_decode_flops(m)
    attn = 4.0 * m.n_attn * m.n_heads * m.head_dim
    for c in ctxs:
        kv_pos += c + 1
        flops += per_token + attn * c
    state = 2 * len(ctxs) * m.state_bytes_per_row
    return m.weight_bytes_per_step + kv_pos * m.kv_bytes_per_position \
        + state, flops


def _ssd_flops(m: Dims, S: int) -> float:
    """Operations of the chunked SSD scan over ``S`` positions of one
    layer, term by term as ``layers/ssm.ssd_scan`` writes them: per chunk
    of Q, C.B over the chunk's pairs, its decay mask, the intra-chunk
    product, the read-out of the carried state, the chunk's contribution
    to the state and the state's decay."""
    Q = min(m.chunk, S)
    H, P, N = m.ssm_heads, m.ssm_head_dim, m.d_state
    per_chunk = (2.0 * Q * Q * N + Q * Q * H + 2.0 * Q * Q * H * P
                 + 2.0 * Q * H * N * P + 2.0 * Q * H * N * P
                 + 2.0 * H * P * N)
    return math.ceil(S / Q) * per_chunk + 2.0 * m.d_conv * m.conv_channels * S


def prefill_flops(m: Dims, S: int) -> float:
    """Operations of a prefill of ``S`` positions: 2 per weight per token,
    the causal scores and values of the attention layers, and the SSD scan
    of every Mamba-2 layer."""
    return 2.0 * m.layers_matmul_params * S \
        + 4.0 * m.n_attn * m.n_heads * m.head_dim * S * (S + 1) / 2 \
        + m.n_mamba * _ssd_flops(m, S)
