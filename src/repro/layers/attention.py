"""Attention layers: GQA (full / sliding-window) with RoPE, decode with KV cache.

All functions are pure; params are plain dict pytrees. Shapes:
  x          (B, S, d_model)
  q          (B, S, n_kv, q_per_kv, hd)   -- GQA grouping kept explicit so the
  k, v       (B, S, n_kv, hd)                n_kv dim is the shardable "heads" dim
  cache k/v  (B, L, n_kv, hd), cache positions (B, L) int32 (-1 = empty)

Long sequences are processed in query chunks (a scan) so the score matrix never
materializes at (S, S); sliding-window layers additionally slice keys to the
window, making local attention linear in S.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import registry as kreg
from repro.layers.norms import rms_norm, softcap
from repro.layers.rope import apply_rope

NEG_INF = -2.3819763e38  # matches gemma reference


@dataclasses.dataclass(frozen=True)
class AttnOpts:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0              # 0 = global (full causal)
    causal: bool = True
    rope_theta: float = 10000.0
    use_rope: bool = True
    softcap: float = 0.0
    qk_norm: bool = False
    query_scale: float = 0.0     # 0 -> head_dim ** -0.5
    q_chunk: int = 256           # query-chunk size for long sequences
    attn_tp: str = "heads"       # "heads" | "seq": TP axis for the score
                                 # einsum; "seq" shards query positions over
                                 # "model" (for kv_heads % tp != 0 archs)
    # tuned Pallas geometry (threaded from ModelConfig.geometry by the
    # stage planner; swept per device class by repro.tuning)
    decode_block_k: int = 512
    flash_block_q: int = 256
    flash_block_k: int = 256
    kernel_force: str = ""       # "" = by backend | kernel|interpret|ref


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, opts: AttnOpts, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    h, g, hd = opts.n_kv_heads, opts.n_heads // opts.n_kv_heads, opts.head_dim
    s = d_model ** -0.5
    p = {
        "wq": jax.random.normal(kq, (d_model, h, g, hd), dtype) * s,
        "wk": jax.random.normal(kk, (d_model, h, hd), dtype) * s,
        "wv": jax.random.normal(kv, (d_model, h, hd), dtype) * s,
        "wo": jax.random.normal(ko, (h, g, hd, d_model), dtype) * s,
    }
    if opts.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


# ---------------------------------------------------------------------------
# Core score/combine helpers
# ---------------------------------------------------------------------------

def _scale(opts: AttnOpts) -> float:
    return opts.query_scale if opts.query_scale else opts.head_dim ** -0.5


def _qkv(p, x, positions, opts: AttnOpts, kv_src=None, kv_pos=None):
    """Project and rope. Returns q (B,S,kv,g,hd), k/v (B,Skv,kv,hd).

    ``kv_src``: source sequence for k/v (cross-attention); defaults to x.
    """
    xs = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhgk->bshgk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", xs, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xs, p["wv"].astype(x.dtype))
    if opts.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if opts.use_rope:
        B, S = x.shape[:2]
        qf = q.reshape(B, S, -1, opts.head_dim)
        qf = apply_rope(qf, positions, opts.rope_theta)
        q = qf.reshape(q.shape)
        k = apply_rope(k, positions if kv_pos is None else kv_pos,
                       opts.rope_theta)
    return q * _scale(opts), k, v


def _attend(q, k, v, mask, opts: AttnOpts):
    """q (B,Sq,kv,g,hd), k/v (B,Sk,kv,hd), mask (B,Sq,Sk) -> (B,Sq,kv,g,hd)."""
    scores = jnp.einsum("bqhgc,bshc->bhgqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = softcap(scores, opts.softcap)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqs,bshc->bqhgc", probs, v)


def _causal_mask(q_pos, k_pos, window: int, causal: bool, k_valid=None):
    """q_pos (B,Sq), k_pos (B,Sk) -> bool (B,Sq,Sk)."""
    diff = q_pos[:, :, None] - k_pos[:, None, :]
    m = diff >= 0 if causal else jnp.ones_like(diff, dtype=bool)
    if window:
        m = m & (diff < window)
    if k_valid is not None:
        m = m & k_valid[:, None, :]
    return m


# ---------------------------------------------------------------------------
# Pallas dispatch (tuned geometry)
# ---------------------------------------------------------------------------

def _decode_kernel_mode(opts: AttnOpts) -> Optional[str]:
    """Pallas mode for the decode sweep: forced, else by backend."""
    if opts.kernel_force:
        return None if opts.kernel_force == "ref" else opts.kernel_force
    return "kernel" if jax.default_backend() == "tpu" else None


def _forward_kernel_mode(opts: AttnOpts) -> Optional[str]:
    """Pallas mode for full-sequence attention. Opt-in only
    (``kernel_force``): attn_forward is shared with training and the flash
    kernel defines no VJP — serving sets the force via ModelConfig.geometry."""
    if opts.kernel_force and opts.kernel_force != "ref":
        return opts.kernel_force
    return None


def _decode_kernel_attend(q, cache, positions, opts: AttnOpts, mode: str):
    """Decode sweep via the Pallas kernel at the tuned ``decode_block_k``.
    q (B,1,kv,g,hd) already query-scaled -> kernel scale=1."""
    from repro.kernels import ops
    B, _, kv, g, hd = q.shape
    qk = q[:, 0].reshape(B, kv * g, hd)
    kk = cache["k"].transpose(0, 2, 1, 3)        # (B, kv, L, hd)
    vk = cache["v"].transpose(0, 2, 1, 3)
    ks = vs = None
    if "k_scale" in cache:
        ks = cache["k_scale"].transpose(0, 2, 1)
        vs = cache["v_scale"].transpose(0, 2, 1)
    o = ops.decode_attention(qk, kk, vk, cache["pos"], positions[:, 0],
                             window=opts.window, scale=1.0,
                             block_k=opts.decode_block_k,
                             k_scale=ks, v_scale=vs, force=mode)
    return o.reshape(B, 1, kv, g, hd)


def _flash_kernel_attend(q, k, v, opts: AttnOpts, mode: str):
    """Prefill attention via the Pallas flash kernel at the tuned
    (block_q, block_k) tiles. Assumes standard prefill positions
    (``arange`` per row — the kernel masks from block offsets)."""
    from repro.kernels import ops
    B, S, kv, g, hd = q.shape
    qk = q.transpose(0, 2, 3, 1, 4).reshape(B, kv * g, S, hd)
    kk = k.transpose(0, 2, 1, 3)                 # (B, kv, S, hd)
    vk = v.transpose(0, 2, 1, 3)
    o = ops.flash_attention(qk, kk, vk, window=opts.window, scale=1.0,
                            softcap=opts.softcap,
                            block_q=opts.flash_block_q,
                            block_k=opts.flash_block_k, force=mode)
    return o.reshape(B, kv, g, S, hd).transpose(0, 3, 1, 2, 4)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill), query-chunked
# ---------------------------------------------------------------------------

def attn_forward(p, x, positions, opts: AttnOpts,
                 kv_src=None, kv_pos=None, kv_valid=None):
    """Full-sequence attention. Returns (y, (k, v)) -- k/v for cache building.

    ``kv_src``/``kv_pos``/``kv_valid``: encoder states for cross-attention.
    """
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, positions, opts, kv_src, kv_pos)
    if kv_src is not None:
        k_pos, k_valid = kv_pos, kv_valid
    else:
        k_pos, k_valid = positions, None

    qc = opts.q_chunk
    fmode = _forward_kernel_mode(opts)
    if opts.attn_tp == "seq":
        # indivisible kv-heads: shard QUERY positions over the model axis so
        # score compute is TP-distributed (heads replicated); k/v gathered.
        q = _shard_q_seq(q)
        k = _gather_seq(k)
        v = _gather_seq(v)
        mask = _causal_mask(positions, k_pos, opts.window, opts.causal,
                            k_valid)
        y = _attend(q, k, v, mask, opts)
    elif (fmode is not None and opts.causal and kv_src is None
          and kreg.check_flash_blocks(S, opts.flash_block_q,
                                      opts.flash_block_k) is None):
        y = _flash_kernel_attend(q, k, v, opts, fmode)
    elif qc and S > qc and S % qc == 0:
        y = _chunked_attend(q, k, v, positions, k_pos, k_valid, opts)
    else:
        mask = _causal_mask(positions, k_pos, opts.window, opts.causal, k_valid)
        y = _attend(q, k, v, mask, opts)
    out = jnp.einsum("bshgk,hgkd->bsd", y, p["wo"].astype(x.dtype))
    return out, (k, v)


def _shard_q_seq(q):
    from jax.sharding import PartitionSpec as P
    for dp in (("pod", "data"), "data", None):
        try:
            return jax.lax.with_sharding_constraint(
                q, P(dp, "model", *([None] * (q.ndim - 2))))
        except Exception:  # noqa: BLE001 - axis not in ambient mesh
            continue
    return q


def _gather_seq(t):
    """Pin k/v to batch-only sharding (seq gathered) BEFORE the q-chunk scan:
    with sequence-parallel activations, XLA otherwise re-all-gathers k/v on
    every chunk iteration inside the while loop (measured 3.9 TB/device of
    all-gather on llava train_4k — 16× the hoisted cost). No-op without a
    mesh."""
    from jax.sharding import PartitionSpec as P
    for dp in (("pod", "data"), "data", None):
        try:
            return jax.lax.with_sharding_constraint(
                t, P(dp, *([None] * (t.ndim - 1))))
        except Exception:  # noqa: BLE001 - axis not in ambient mesh
            continue
    return t


def _chunked_attend(q, k, v, q_pos, k_pos, k_valid, opts: AttnOpts):
    """Scan over query chunks; local layers slice keys to the window."""
    B, S = q.shape[:2]
    qc = opts.q_chunk
    n_chunks = S // qc
    w = opts.window
    if opts.attn_tp == "heads":
        # hoist the k/v seq-gather out of the chunk loop (Megatron-SP
        # residuals are seq-sharded); "none" = pure-DP, no TP constraints
        k = _gather_seq(k)
        v = _gather_seq(v)

    use_local_slice = bool(w) and w < S and k.shape[1] == S
    if use_local_slice:
        # Pad keys on the left by `w` so chunk i reads keys [i*qc - w, i*qc + qc).
        pad = [(0, 0), (w, 0), (0, 0), (0, 0)]
        k_pad = jnp.pad(k, pad)
        v_pad = jnp.pad(v, pad)
        kp_pad = jnp.pad(k_pos, [(0, 0), (w, 0)], constant_values=-1)
        kval_pad = jnp.pad(jnp.ones((B, S), bool) if k_valid is None else k_valid,
                           [(0, 0), (w, 0)], constant_values=False)

        def body(carry, i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
            qp = jax.lax.dynamic_slice_in_dim(q_pos, i * qc, qc, axis=1)
            ks = jax.lax.dynamic_slice_in_dim(k_pad, i * qc, qc + w, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v_pad, i * qc, qc + w, axis=1)
            kps = jax.lax.dynamic_slice_in_dim(kp_pad, i * qc, qc + w, axis=1)
            kvs = jax.lax.dynamic_slice_in_dim(kval_pad, i * qc, qc + w, axis=1)
            mask = _causal_mask(qp, kps, w, opts.causal, kvs)
            return carry, _attend(qs, ks, vs, mask, opts)
    else:
        def body(carry, i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
            qp = jax.lax.dynamic_slice_in_dim(q_pos, i * qc, qc, axis=1)
            mask = _causal_mask(qp, k_pos, w, opts.causal, k_valid)
            return carry, _attend(qs, k, v, mask, opts)

    # checkpoint: a chunk's backward recomputes its (qc, S) score matrix
    # instead of storing scores/probs for every chunk (tens of GB at 4k+)
    _, ys = jax.lax.scan(jax.checkpoint(body), None, jnp.arange(n_chunks))
    # ys: (n_chunks, B, qc, kv, g, hd) -> (B, S, kv, g, hd)
    return jnp.moveaxis(ys, 0, 1).reshape(q.shape)


# ---------------------------------------------------------------------------
# Decode (single new token against a KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, cache_len: int, opts: AttnOpts, dtype,
                  quant: bool = False):
    """KV cache. ``quant`` stores k/v as int8 with per-(b,l,h) fp32 scales —
    halves cache bytes per device (2× serving density); the Pallas
    ``decode_attention`` kernel reads the int8 form directly on TPU."""
    shp = (batch, cache_len, opts.n_kv_heads, opts.head_dim)
    cache = {
        "k": jnp.zeros(shp, jnp.int8 if quant else dtype),
        "v": jnp.zeros(shp, jnp.int8 if quant else dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }
    if quant:
        cache["k_scale"] = jnp.ones(shp[:3], jnp.float32)
        cache["v_scale"] = jnp.ones(shp[:3], jnp.float32)
    return cache


def _quant_rows(x):
    """(…, hd) -> int8 values + fp32 scale over the last dim."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _deq(k, scale, dtype):
    return (k.astype(jnp.float32) * scale[..., None]).astype(dtype)


def fill_kv_cache(cache, k, v, positions):
    """Write prefill k/v (B,S,kv,hd) into the cache (ring for local layers)."""
    L = cache["k"].shape[1]
    S = k.shape[1]
    if S <= L:
        idx = positions % L                       # (B, S)
    else:                                         # keep last L entries (ring)
        k, v, positions = k[:, -L:], v[:, -L:], positions[:, -L:]
        idx = positions % L
    b = jnp.arange(k.shape[0])[:, None]
    out = dict(cache)
    if "k_scale" in cache:
        kq, ks = _quant_rows(k)
        vq, vs = _quant_rows(v)
        out["k"] = cache["k"].at[b, idx].set(kq)
        out["v"] = cache["v"].at[b, idx].set(vq)
        out["k_scale"] = cache["k_scale"].at[b, idx].set(ks)
        out["v_scale"] = cache["v_scale"].at[b, idx].set(vs)
    else:
        out["k"] = cache["k"].at[b, idx].set(k)
        out["v"] = cache["v"].at[b, idx].set(v)
    out["pos"] = cache["pos"].at[b, idx].set(positions)
    return out


def init_paged_kv_pool(n_pages: int, page_size: int, opts: AttnOpts, dtype,
                       quant: bool = False):
    """Paged KV pool: one shared page set instead of per-sequence rows.
    Page 0 is reserved by the engine as the null/scratch page — unused
    block-table entries point at it, and inactive batch rows write their
    (discarded) k/v there with pos -1, so gathers through any table never
    see a valid-looking stale position."""
    shp = (n_pages, page_size, opts.n_kv_heads, opts.head_dim)
    pool = {
        "k": jnp.zeros(shp, jnp.int8 if quant else dtype),
        "v": jnp.zeros(shp, jnp.int8 if quant else dtype),
        "pos": jnp.full((n_pages, page_size), -1, jnp.int32),
    }
    if quant:
        pool["k_scale"] = jnp.ones(shp[:3], jnp.float32)
        pool["v_scale"] = jnp.ones(shp[:3], jnp.float32)
    return pool


def attn_decode_paged(p, x, positions, cache, block_tables, opts: AttnOpts):
    """Paged-cache decode step. x (B,1,d); positions (B,1) absolute with -1
    for inactive batch rows; cache leaves (P, ps, kv, hd) / pos (P, ps);
    block_tables (B, nb) int32 page ids (0 pads unused entries).

    The new k/v lands at page ``block_tables[b, pos // ps]`` offset
    ``pos % ps`` — the engine guarantees that page is privately owned
    (copy-on-write happens host-side before a shared page is written)."""
    B = x.shape[0]
    ps = cache["k"].shape[1]
    q, k, v = _qkv(p, x, positions, opts)        # k/v (B,1,kv,hd)
    quant = "k_scale" in cache
    pos = positions[:, 0]
    active = pos >= 0
    safe = jnp.maximum(pos, 0)
    pid = jnp.take_along_axis(block_tables, (safe // ps)[:, None],
                              axis=1)[:, 0]                      # (B,)
    # inactive rows write the reserved scratch page with pos -1
    pid = jnp.where(active, pid, 0)
    off = jnp.where(active, safe % ps, 0)
    new = dict(cache)
    if quant:
        kq, ks = _quant_rows(k[:, 0])
        vq, vs = _quant_rows(v[:, 0])
        new["k"] = cache["k"].at[pid, off].set(kq)
        new["v"] = cache["v"].at[pid, off].set(vq)
        new["k_scale"] = cache["k_scale"].at[pid, off].set(ks)
        new["v_scale"] = cache["v_scale"].at[pid, off].set(vs)
    else:
        new["k"] = cache["k"].at[pid, off].set(k[:, 0])
        new["v"] = cache["v"].at[pid, off].set(v[:, 0])
    new["pos"] = cache["pos"].at[pid, off].set(jnp.where(active, pos, -1))
    cache = new
    nb = block_tables.shape[1]
    chunk = sweep_chunk_cols(B, nb, ps, opts.n_kv_heads, opts.head_dim,
                             cache["k"].dtype.itemsize)
    y = _paged_sweep(q, cache, block_tables, positions, opts, chunk)
    out = jnp.einsum("bshgk,hgkd->bsd", y, p["wo"].astype(x.dtype))
    return out, cache


# K and V bytes that one chunk of the paged decode sweep gathers for the
# whole batch. A block table whose K and V fit in this is swept in one
# pass (smollm-135m's at 4 slots of 2048 positions: 6.3 MB). phi3-mini's
# (4 slots, 4096 positions, 32 heads of 96) goes in chunks of 256
# positions, the fastest of 256, 512 and 1024 on a TPU v5e: a decode step
# of rows at 1100-2100 positions took 36.5, 39.0 and 41.6 ms.
SWEEP_CHUNK_BYTES = 12 << 20


def sweep_chunk_cols(batch: int, n_cols: int, page_size: int, n_kv_heads: int,
                     head_dim: int, itemsize: int) -> int:
    """Block-table columns in one chunk of the paged decode sweep: as many
    as hold ``SWEEP_CHUNK_BYTES`` of the batch's K and V, or the whole
    table when it holds less."""
    per_col = 2 * batch * page_size * n_kv_heads * head_dim * itemsize
    return max(1, min(n_cols, SWEEP_CHUNK_BYTES // per_col))


def sweep_chunks(pos, page_size: int, n_cols: int, window: int, chunk: int,
                 xp=jnp):
    """Chunks ``[lo, hi)`` of ``chunk`` block-table columns that the paged
    decode sweep covers for positions ``pos`` (B,), -1 for an inactive
    row: from the chunk holding the first position any active row's
    window reaches to the one holding the furthest active position. A
    table of one chunk is swept whole. Pure over ``xp``: ``jnp`` on the
    device, ``numpy`` for the engine's count of what the device swept."""
    if chunk >= n_cols:
        return 0, 1
    active = pos >= 0
    hi = xp.max(xp.where(active, pos, -1)) // page_size // chunk + 1
    if not window:
        return 0, hi
    first = xp.min(xp.where(active, xp.maximum(pos - window + 1, 0),
                            n_cols * page_size))
    return xp.minimum(first // page_size // chunk, hi), hi


def swept_cols(pos, page_size: int, n_cols: int, window: int,
               chunk: int) -> int:
    """Block-table columns the paged decode sweep covers for host
    positions ``pos`` (a numpy array)."""
    lo, hi = sweep_chunks(pos, page_size, n_cols, window, chunk, xp=np)
    return max(0, min(int(hi) * chunk, n_cols) - int(lo) * chunk)


def _paged_sweep(q, cache, block_tables, positions, opts: AttnOpts,
                 chunk: int):
    """Attention of the decode queries q (B,1,kv,g,hd) over the pages the
    batch can see: ``chunk`` block-table columns at a time, over the
    chunks ``sweep_chunks`` bounds on the device, with an online softmax
    (running max, running sum, f32 accumulator) across chunks. K and V
    enter both dots in their stored dtype with f32 accumulation, so no
    f32 copy of the gathered pages is made; an int8 pool is dequantised
    chunk by chunk. Unused table entries point at the null page (pos -1),
    which the mask drops, as do positions past a row's own or outside its
    window.

    The Pallas paged kernel (kernels/decode_attention.py) is not used
    here: it takes one grid step per (row, head, page), 32768 a phi3-mini
    layer, and reads a (P, kv, ps, hd) pool, not this (P, ps, kv, hd) one."""
    B, _, kv, g, hd = q.shape
    nb = block_tables.shape[1]
    lo, hi = sweep_chunks(positions[:, 0], cache["k"].shape[1], nb,
                          opts.window, chunk)
    bt = jnp.pad(block_tables, ((0, 0), (0, -nb % chunk)))
    # With one query head per KV head both dots are vector-matrix
    # products, which XLA rewrites as f32 multiply-reduces over an f32
    # copy of the gathered pages. A second, zero query row keeps them on
    # the MXU in the pool's dtype; its scores and outputs are dropped.
    extra = 1 if g == 1 else 0
    qr = jnp.pad(q, ((0, 0), (0, extra), (0, 0), (0, 0), (0, 0)))

    def update(c, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(bt, c * chunk, chunk, axis=1)
        with jax.named_scope("rc3e.attn.gather"):
            k, v = cache["k"][cols], cache["v"][cols]    # (B, C, ps, kv, hd)
            if "k_scale" in cache:
                k = _deq(k, cache["k_scale"][cols], q.dtype)
                v = _deq(v, cache["v_scale"][cols], q.dtype)
            kpos = cache["pos"][cols].reshape(B, -1)
        k = k.reshape(B, -1, kv, hd)
        v = v.reshape(B, -1, kv, hd)
        s = jnp.einsum("bqhgc,bshc->bhgqs", qr, k,
                       preferred_element_type=jnp.float32)[..., :1, :]
        s = softcap(s, opts.softcap)
        mask = _causal_mask(positions, kpos, opts.window, opts.causal,
                            k_valid=kpos >= 0)[:, None, None]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        pr = jnp.pad(pr.astype(v.dtype),
                     ((0, 0), (0, 0), (0, 0), (0, extra), (0, 0)))
        pv = jnp.einsum("bhgqs,bshc->bhgqc", pr, v,
                        preferred_element_type=jnp.float32)[..., :1, :]
        return m_new, l, acc * alpha + pv

    carry = (jnp.full((B, kv, g, 1, 1), NEG_INF, jnp.float32),
             jnp.zeros((B, kv, g, 1, 1), jnp.float32),
             jnp.zeros((B, kv, g, 1, hd), jnp.float32))
    if chunk >= nb:
        carry = update(0, carry)
    else:
        carry = jax.lax.fori_loop(lo, hi, update, carry)
    _, l, acc = carry
    y = acc / jnp.maximum(l, 1e-30)                       # (B, kv, g, 1, hd)
    return jnp.moveaxis(y, 3, 1).astype(q.dtype)


def attn_decode(p, x, positions, cache, opts: AttnOpts, update_cache=True):
    """x (B,1,d); positions (B,1) absolute. Returns (y, cache').

    With a quantized cache (int8 + scales) the XLA path dequantizes before
    the score dots; on TPU, ``kernels.ops.decode_attention`` consumes the
    int8 arrays directly (dequant in VMEM).
    """
    B = x.shape[0]
    q, k, v = _qkv(p, x, positions, opts)        # k/v (B,1,kv,hd)
    quant = "k_scale" in cache
    if update_cache:
        L = cache["k"].shape[1]
        idx = (positions[:, 0] % L)
        b = jnp.arange(B)
        new = dict(cache)
        if quant:
            kq, ks = _quant_rows(k[:, 0])
            vq, vs = _quant_rows(v[:, 0])
            new["k"] = cache["k"].at[b, idx].set(kq)
            new["v"] = cache["v"].at[b, idx].set(vq)
            new["k_scale"] = cache["k_scale"].at[b, idx].set(ks)
            new["v_scale"] = cache["v_scale"].at[b, idx].set(vs)
        else:
            new["k"] = cache["k"].at[b, idx].set(k[:, 0])
            new["v"] = cache["v"].at[b, idx].set(v[:, 0])
        new["pos"] = cache["pos"].at[b, idx].set(positions[:, 0])
        cache = new
    dmode = _decode_kernel_mode(opts)
    # soft-capped scores have no kernel yet: those layers decode on XLA
    if dmode is not None and opts.causal and not opts.softcap:
        reason = kreg.check_decode_block(cache["k"].shape[1],
                                         opts.decode_block_k)
        if reason is not None:
            raise ValueError(f"decode kernel cannot sweep this cache: "
                             f"{reason}")
        y = _decode_kernel_attend(q, cache, positions, opts, dmode)
    else:
        if quant:
            k_all = _deq(cache["k"], cache["k_scale"], x.dtype)
            v_all = _deq(cache["v"], cache["v_scale"], x.dtype)
        else:
            k_all, v_all = cache["k"], cache["v"]
        kpos = cache["pos"]
        mask = _causal_mask(positions, kpos, opts.window, opts.causal,
                            k_valid=kpos >= 0)
        y = _attend(q, k_all, v_all, mask, opts)
    out = jnp.einsum("bshgk,hgkd->bsd", y, p["wo"].astype(x.dtype))
    return out, cache
