"""The float32 reference of the served model, and the helpers every
architecture's reference layer is written with.

An architecture module (``bench/arch/<arch>.py``, found by ``spec.arch``)
writes its layer, logits and embedding straight in ``jax.numpy`` with
these helpers: float32 at ``Precision.HIGHEST``, no cache, no kernels.
``served_gaps`` walks its layers one after another. The reference imports
nothing of the program: it draws its weights from the seed
(``weights.layer``/``weights.top``) and reads only the prompts and the
tokens the program served.

``quant="fp8"`` runs the same pass with every matmul's weights and inputs
rounded to float8 e4m3 (one scale per tensor or per row): the control,
one precision step below the served bfloat16. The helpers are shared, so
the control means the same for every architecture.
"""
from __future__ import annotations

from types import ModuleType
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights

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


def served_gaps(arch: ModuleType, m, seed: int,
                seqs: Sequence[Tuple[np.ndarray, Sequence[int]]],
                control: bool = False
                ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
    """For each (prompt, served tokens): at every served position, how far
    the served token's reference logit lies below the reference's best,
    for the architecture ``arch`` at sizes ``m``. With ``control``, also
    the same gap for the token the fp8 pass puts first. Runs layer by
    layer over all sequences, so only one layer's weights are on the
    device at a time."""
    lay = arch.layout(m)
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
        emb = arch.embed(m, top, jnp.asarray(toks))
        for q in streams:
            xs[q].append(emb)
        spans.append((len(prompt) - 1, S, np.asarray(out, np.int64)))
    for r in range(m.n_layers):
        p = weights.layer(lay, seed, *arch.layer_at(m, r))
        for q in streams:
            xs[q] = [arch.layer(m, r, p, x, q) for x in xs[q]]
        del p
    ref_gaps, ctl_gaps = [], []
    for i, (a, b, out) in enumerate(spans):
        n = len(out)
        # the served rows, padded to a multiple of PAD: few compiled shapes
        rows = jnp.asarray(np.minimum(a + np.arange(-(-n // PAD) * PAD),
                                      xs[None][i].shape[0] - 1))
        ref = np.asarray(arch.logits(m, top, xs[None][i][rows], None))[:n]
        best = ref.max(axis=-1)
        ref_gaps.append(best - ref[np.arange(n), out])
        if control:
            ctl = np.asarray(arch.logits(m, top, xs["fp8"][i][rows],
                                         "fp8"))[:n]
            first = ctl.argmax(axis=-1)
            ctl_gaps.append(best - ref[np.arange(n), first])
    return ref_gaps, (ctl_gaps if control else None)
