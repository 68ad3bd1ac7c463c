"""Mamba2 (state-space duality) block: chunked SSD scan + single-token decode.

Follows the minimal-SSD formulation (Dao & Gu 2024): within a chunk the
computation is a masked (B,Q,Q,H) "attention-like" matmul; across chunks a
scan carries the (B,H,P,N) state. Decode is the pure recurrence
  state' = exp(dt*A) * state + dt * x ⊗ B ;  y = C · state' + D * x
with a (d_conv-1)-deep ring buffer for the causal conv.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.layers.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class SSMOpts:
    d_model: int
    cfg: SSMConfig
    tp: bool = True          # False = pure-DP mode, no TP constraints
    eps: float = 1e-6        # the gated RMSNorm's epsilon

    @property
    def d_inner(self) -> int:
        return self.cfg.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.cfg.head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.cfg.n_groups * self.cfg.d_state


def init_ssm(key, opts: SSMOpts, dtype=jnp.float32):
    c = opts.cfg
    d, d_in, H = opts.d_model, opts.d_inner, opts.n_heads
    conv_ch = opts.conv_channels
    k1, k2, k3, k4 = jax.random.split(key, 4)
    proj_out = 2 * d_in + 2 * c.n_groups * c.d_state + H
    lo, hi = c.a_init_range
    a = jnp.exp(jax.random.uniform(k4, (H,), jnp.float32,
                                   jnp.log(lo), jnp.log(hi)))
    return {
        "in_proj": jax.random.normal(k1, (d, proj_out), dtype) * d ** -0.5,
        "conv_w": jax.random.normal(k2, (c.d_conv, conv_ch), dtype) * 0.1,
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.log(a),
        "D": jnp.ones((H,), jnp.float32),
        # gated RMSNorm weight, stored as w - 1 and applied as 1 + w
        "norm": jnp.zeros((d_in,), dtype),
        "out_proj": jax.random.normal(k3, (d_in, d), dtype) * d_in ** -0.5,
    }


def _shard_tail(t, tail_axis_from_end: int):
    """Constrain a (B, S, ...) ssm tensor: batch over dp, the channel/head
    dim (``tail_axis_from_end`` from the right) over "model". GSPMD loses
    propagation at the grouped conv, replicating (B, S, conv_ch) fp32
    tensors (1.9 GB each on zamba2 train_4k) without this. No-op on CPU."""
    from jax.sharding import PartitionSpec as P
    spec_tail = [None] * (t.ndim - 1)
    spec_tail[-tail_axis_from_end] = "model"
    for dp in (("pod", "data"), "data", None):
        try:
            return jax.lax.with_sharding_constraint(t, P(dp, *spec_tail))
        except Exception:  # noqa: BLE001 - axis not in ambient mesh
            continue
    return t


def _causal_conv(x, w, b):
    """Depthwise causal conv. x (B,S,C), w (K,C)."""
    K = w.shape[0]
    xp = jnp.pad(x, [(0, 0), (K - 1, 0), (0, 0)])
    out = jax.lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return out + b.astype(x.dtype)


def _split_proj(zxbcdt, opts: SSMOpts):
    c, d_in, H = opts.cfg, opts.d_inner, opts.n_heads
    gn = c.n_groups * c.d_state
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in: d_in + d_in + 2 * gn]
    dt = zxbcdt[..., -H:]
    return z, xbc, dt


def _split_xbc(xbc, opts: SSMOpts):
    c, d_in = opts.cfg, opts.d_inner
    gn = c.n_groups * c.d_state
    xs = xbc[..., :d_in]
    Bm = xbc[..., d_in: d_in + gn]
    Cm = xbc[..., d_in + gn:]
    B = xs.shape[0]
    S = xs.shape[1] if xs.ndim == 3 else 1
    xs = xs.reshape(B, S, opts.n_heads, c.head_dim)
    Bm = Bm.reshape(B, S, c.n_groups, c.d_state)
    Cm = Cm.reshape(B, S, c.n_groups, c.d_state)
    return xs, Bm, Cm


def ssd_scan(xs, dt, A, Bm, Cm, D, chunk: int, init_state=None):
    """Chunked SSD. xs (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,G,N), D (H,).

    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    Bsz, S, H, P = xs.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    Q = min(chunk, S)
    orig_S = S
    if S % Q:
        # pad with dt=0 steps: dA=exp(0)=1 keeps state, dtx=0 adds nothing
        pad = Q - S % Q
        xs = jnp.pad(xs, [(0, 0), (0, pad), (0, 0), (0, 0)])
        dt = jnp.pad(dt, [(0, 0), (0, pad), (0, 0)])
        Bm = jnp.pad(Bm, [(0, 0), (0, pad), (0, 0), (0, 0)])
        Cm = jnp.pad(Cm, [(0, 0), (0, pad), (0, 0), (0, 0)])
        S = S + pad
    nc = S // Q

    def to_chunks(a):
        return jnp.moveaxis(a.reshape((Bsz, nc, Q) + a.shape[2:]), 1, 0)

    xc, dtc, Bc, Cc = map(to_chunks, (xs, dt, Bm, Cm))
    state0 = (jnp.zeros((Bsz, H, P, N), jnp.float32)
              if init_state is None else init_state.astype(jnp.float32))

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def body(state, inp):
        xq, dtq, Bq, Cq = inp                       # (B,Q,H,P) (B,Q,H) (B,Q,G,N)
        dA = dtq.astype(jnp.float32) * A            # (B,Q,H), negative
        cums = jnp.cumsum(dA, axis=1)               # (B,Q,H)
        seg = cums[:, :, None, :] - cums[:, None, :, :]     # (B,Qi,Qj,H)
        # mask BEFORE exp: upper-triangle seg is positive (dA < 0), exp(seg)
        # overflows to inf and inf*0 in the backward of `where` makes every
        # SSM gradient NaN on the very first step
        seg = jnp.where(tri[None, :, :, None], seg, -jnp.inf)
        L = jnp.exp(seg)
        CB = jnp.einsum("bqgn,bkgn->bqkg", Cq, Bq,
                        preferred_element_type=jnp.float32)
        M = jnp.repeat(CB, hpg, axis=-1) * L        # (B,Q,Q,H)
        dtx = (xq * dtq[..., None]).astype(jnp.float32)     # (B,Q,H,P)
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", M, dtx)
        # inter-chunk: decay from chunk start to position i
        decay_in = jnp.exp(cums)                    # (B,Q,H)
        Ch = jnp.repeat(Cq, hpg, axis=2)            # (B,Q,H,N)
        y_inter = jnp.einsum("bqhn,bhpn->bqhp", Ch.astype(jnp.float32),
                             state) * decay_in[..., None]
        # state update
        total = cums[:, -1]                         # (B,H)
        decay_out = jnp.exp(total[:, None] - cums)  # (B,Q,H): prod_{l>j} dA
        Bh = jnp.repeat(Bq, hpg, axis=2)            # (B,Q,H,N)
        contrib = jnp.einsum("bqhn,bqhp->bhpn",
                             (Bh * decay_out[..., None]).astype(jnp.float32),
                             dtx)
        state = state * jnp.exp(total)[:, :, None, None] + contrib
        y = y_intra + y_inter + D[None, None, :, None] * xq.astype(jnp.float32)
        return state, y.astype(xs.dtype)

    # checkpoint: recompute the (B,Q,Q,H) chunk matrices in backward instead
    # of storing them for all chunks (7.5 GB/layer on zamba2 train_4k)
    state, yc = jax.lax.scan(jax.checkpoint(body), state0,
                             (xc, dtc, Bc, Cc))
    # yc: (nc, B, Q, H, P) -> (B, S, H, P)
    y = jnp.moveaxis(yc, 0, 1).reshape(Bsz, S, H, P)[:, :orig_S]
    return y, state


def _conv_tail(xbc, n, k: int):
    """The ``k`` conv inputs before position ``n`` (B,) of each row, zeros
    where a row has fewer: what the decode conv cache holds after ``n``
    positions."""
    xp = jnp.pad(xbc, [(0, 0), (k, 0), (0, 0)])
    return jax.vmap(lambda r, i: jax.lax.dynamic_slice_in_dim(r, i, k))(
        xp, n)


def ssm_forward(p, x, opts: SSMOpts, init_state=None, valid=None):
    """Full-sequence Mamba2 block. Returns (y, (ssd_state, conv_tail)).

    ``valid`` (B, S) bool marks each row's real positions, a prefix: the
    rest is padding, which runs with ``dt = 0`` and ``x = 0`` so that it
    leaves the state as the real positions left it, and the conv tail is
    taken at each row's real length."""
    Bsz, S, d = x.shape
    c = opts.cfg
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xbc, dt = _split_proj(zxbcdt, opts)
    if valid is None:
        conv_tail = xbc[:, -(c.d_conv - 1):, :]      # decode conv cache
    else:
        conv_tail = _conv_tail(xbc, jnp.sum(valid, axis=1, dtype=jnp.int32),
                               c.d_conv - 1)
    if opts.tp:
        xbc = _shard_tail(xbc, 1)                    # channels over model
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    if opts.tp:
        xbc = _shard_tail(xbc, 1)
    xs, Bm, Cm = _split_xbc(xbc, opts)
    if opts.tp:
        xs = _shard_tail(xs, 2)                      # ssd heads over model
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
        xs = jnp.where(valid[..., None, None], xs, 0)
    A = -jnp.exp(p["A_log"])
    y, state = ssd_scan(xs, dt, A, Bm, Cm, p["D"], c.chunk, init_state)
    y = y.reshape(Bsz, S, opts.d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], opts.eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, (state, conv_tail)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_ssm_cache(batch: int, opts: SSMOpts, dtype):
    c = opts.cfg
    return {
        "state": jnp.zeros((batch, opts.n_heads, c.head_dim, c.d_state),
                           jnp.float32),
        "conv": jnp.zeros((batch, c.d_conv - 1, opts.conv_channels), dtype),
    }


def ssm_decode(p, x, cache, opts: SSMOpts):
    """x (B,1,d). Returns (y (B,1,d), cache')."""
    Bsz = x.shape[0]
    c = opts.cfg
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xbc_t, dt = _split_proj(zxbcdt, opts)         # xbc_t (B,1,C)
    window = jnp.concatenate([cache["conv"], xbc_t], axis=1)  # (B,K,C)
    new_conv = window[:, 1:, :]
    w = p["conv_w"].astype(x.dtype)                  # (K,C)
    conv_out = jnp.einsum("bkc,kc->bc", window, w) + p["conv_b"].astype(x.dtype)
    xbc = jax.nn.silu(conv_out)[:, None, :]          # (B,1,C)
    xs, Bm, Cm = _split_xbc(xbc, opts)               # (B,1,H,P),(B,1,G,N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])[:, 0]  # (B,H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                             # (B,H)
    hpg = opts.n_heads // c.n_groups
    Bh = jnp.repeat(Bm[:, 0], hpg, axis=1)           # (B,H,N)
    Ch = jnp.repeat(Cm[:, 0], hpg, axis=1)
    dtx = (xs[:, 0] * dt[..., None]).astype(jnp.float32)   # (B,H,P)
    state = (cache["state"] * dA[:, :, None, None]
             + jnp.einsum("bhp,bhn->bhpn", dtx, Bh.astype(jnp.float32)))
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch.astype(jnp.float32))
    y = y + p["D"][None, :, None] * xs[:, 0].astype(jnp.float32)
    y = y.reshape(Bsz, 1, opts.d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], opts.eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, {"state": state, "conv": new_conv}
