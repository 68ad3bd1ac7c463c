"""Plain float32 reference forward pass of a dense decoder-only LM with
grouped-query attention (global, or a sliding window of ``Dims.window``
positions: a query attends the keys less than the window behind it),
RoPE and a SiLU-gated MLP, and the comparison that decides ``correct``.

Written straight in ``jax.numpy``: one layer after another, the whole
masked score matrix, no cache, no kernels, float32 at
``Precision.HIGHEST``. It imports nothing of the program: it draws its
weights from the seed (``weights.layer``/``weights.top``) and reads only
the prompts and the tokens the program served. RMSNorm weights are
stored as ``w - 1`` and applied as ``1 + w``; RoPE rotates the two halves
of each head.

``quant="fp8"`` runs the same pass with every matmul's weights and inputs
rounded to float8 e4m3 (one scale per tensor or per row): the control,
one precision step below the served bfloat16.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights
from dims import Dims

HI = jax.lax.Precision.HIGHEST
PAD = 256          # sequences are padded to a multiple: few compiled shapes


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (S, k) @ w (k, n) in float32, or from fp8-rounded operands."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    S, _, hd = x.shape
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(m: Dims, top, h, quant):
    h = _rms_norm(h, top["final_norm"], m.norm_eps)
    head = top["embed/tok"].T if m.tied else top["head"]
    return _mm(h, head, quant)


def served_gaps(m: Dims, seed: int,
                seqs: Sequence[Tuple[np.ndarray, Sequence[int]]],
                control: bool = False
                ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
    """For each (prompt, served tokens): at every served position, how far
    the served token's reference logit lies below the reference's best.
    With ``control``, also the same gap for the token the fp8 pass puts
    first. Runs layer by layer over all sequences, so only one layer's
    weights are on the device at a time."""
    lay = weights.layout(m)
    top = weights.top(lay, seed)
    streams = [None, "fp8"] if control else [None]
    xs = {q: [] for q in streams}
    spans = []
    for prompt, out in seqs:
        full = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(out, np.int32)])[:-1]
        S = len(full)
        toks = np.zeros((-(-S // PAD) * PAD,), np.int32)
        toks[:S] = full
        emb = top["embed/tok"][jnp.asarray(toks)]
        for q in streams:
            xs[q].append(emb)
        spans.append((len(prompt) - 1, S, np.asarray(out, np.int64)))
    for r in range(m.n_layers):
        p = weights.layer(lay, seed, r)
        for q in streams:
            xs[q] = [_layer(m, p, x, q) for x in xs[q]]
        del p
    ref_gaps, ctl_gaps = [], []
    for i, (a, b, out) in enumerate(spans):
        n = len(out)
        # the served rows, padded to a multiple of PAD: few compiled shapes
        rows = jnp.asarray(np.minimum(a + np.arange(-(-n // PAD) * PAD),
                                      xs[None][i].shape[0] - 1))
        ref = np.asarray(_logits(m, top, xs[None][i][rows], None))[:n]
        best = ref.max(axis=-1)
        ref_gaps.append(best - ref[np.arange(n), out])
        if control:
            ctl = np.asarray(_logits(m, top, xs["fp8"][i][rows], "fp8"))[:n]
            first = ctl.argmax(axis=-1)
            ctl_gaps.append(best - ref[np.arange(n), first])
    return ref_gaps, (ctl_gaps if control else None)
