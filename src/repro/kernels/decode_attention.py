"""KV-cache decode attention Pallas kernel: one new query token per sequence
against a (possibly ring-buffered) cache, GQA, online softmax over cache
blocks. This is the serve_step hot loop (decode_32k / long_500k cells).

Layout: q (B, Hq, D); k/v (B, Hkv, L, D); kpos (B, L) absolute positions
(-1 = empty); cur (B,) current positions. Query heads are grouped per KV
head: grid (B·Hkv, L/bk), each step sweeps one (bk, D) cache block for the
``g = Hq/Hkv`` query heads that share it, accumulators (g, D)/(g, 1) in
VMEM scratch across the sweep. Every block's last two dims are either
(8, 128)-tileable or the array's full extent — what the TPU compiler
requires — and ``cur`` is scalar-prefetched into SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry as kreg

NEG_INF = -1e30


def _sweep_update(q, k, v, kpos, cur, o_ref, acc_ref, m_ref, l_ref, *,
                  ik, n_k: int, window: int, k_scale=None, v_scale=None):
    """One cache-block step of the online softmax for a group of query
    heads: q (g, D), k/v (bk, D) in fp32, kpos (1, bk), cur a scalar.
    ``k_scale``/``v_scale`` (1, bk) are int8 row scales, folded into the
    scores and the probabilities (exactly dequantizing k and v). Shared by
    the dense and block-table-paged sweeps."""
    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (g, bk)
    if k_scale is not None:
        s = s * k_scale
    mask = (kpos >= 0) & (kpos <= cur)                            # (1, bk)
    if window:
        mask &= (cur - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                           # (g, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _decode_kernel(cur_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref, acc_ref,
                   m_ref, l_ref, *, n_k: int, n_kv: int, scale: float,
                   window: int):
    _sweep_update(q_ref[0].astype(jnp.float32) * scale,
                  k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
                  kpos_ref[0], cur_ref[pl.program_id(0) // n_kv], o_ref,
                  acc_ref, m_ref, l_ref, ik=pl.program_id(1), n_k=n_k,
                  window=window)


def _decode_kernel_q8(cur_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, kpos_ref,
                      o_ref, acc_ref, m_ref, l_ref, *, n_k: int, n_kv: int,
                      scale: float, window: int):
    """int8-quantized cache variant: k/v arrive as int8 blocks + per-row
    fp32 scales and are dequantized in VMEM — HBM traffic for the cache
    sweep is halved vs bf16 (the decode roofline's dominant term)."""
    _sweep_update(q_ref[0].astype(jnp.float32) * scale,
                  k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
                  kpos_ref[0], cur_ref[pl.program_id(0) // n_kv], o_ref,
                  acc_ref, m_ref, l_ref, ik=pl.program_id(1), n_k=n_k,
                  window=window, k_scale=ks_ref[0], v_scale=vs_ref[0])


def _scratch(g: int, D: int):
    return [pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32)]


def decode_attention(q, k, v, kpos, cur, *, window: int = 0,
                     scale: float = 0.0,
                     block_k: int = kreg.DECODE_BLOCK_DEFAULT,
                     k_scale=None, v_scale=None, interpret: bool = False):
    """q (B, Hq, D); k/v (B, Hkv, L, D); kpos (B, L); cur (B,).

    ``block_k`` is a tunable geometry knob — legal range and divisibility
    rule live in ``kernels.registry``. ``k_scale``/``v_scale`` (B, Hkv, L)
    enable the int8-cache path: k/v are int8 and dequantized blockwise in
    VMEM. Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    g = Hq // Hkv
    scale = scale or D ** -0.5
    bk = min(block_k, L)
    reason = kreg.check_decode_block(L, block_k)
    assert L % bk == 0 and reason is None, (L, bk, reason)
    grid = (B * Hkv, L // bk)
    quant = k_scale is not None

    def q_map(h, ik, cur):
        return (h, 0, 0)

    def kv_map(h, ik, cur):
        return (h, ik, 0)

    def row_map(h, ik, cur):
        return (h, 0, ik)

    in_specs = [
        pl.BlockSpec((1, g, D), q_map),
        pl.BlockSpec((1, bk, D), kv_map),
        pl.BlockSpec((1, bk, D), kv_map),
    ]
    operands = [q.reshape(B * Hkv, g, D), k.reshape(B * Hkv, L, D),
                v.reshape(B * Hkv, L, D)]
    kernel = _decode_kernel_q8 if quant else _decode_kernel
    if quant:
        in_specs += [pl.BlockSpec((1, 1, bk), row_map),
                     pl.BlockSpec((1, 1, bk), row_map)]
        operands += [k_scale.reshape(B * Hkv, 1, L),
                     v_scale.reshape(B * Hkv, 1, L)]
    in_specs.append(pl.BlockSpec((1, 1, bk),
                                 lambda h, ik, cur: (h // Hkv, 0, ik)))
    operands.append(kpos.reshape(B, 1, L))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, D), q_map),
        scratch_shapes=_scratch(g, D),
    )
    out = pl.pallas_call(
        functools.partial(kernel, n_k=grid[1], n_kv=Hkv, scale=scale,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, g, D),
                                       q.dtype if not quant else jnp.float32),
        interpret=interpret,
    )(cur.astype(jnp.int32), *operands)
    return out.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged variant: the cache lives in a shared page pool, each sequence's
# pages located through a block table (scalar-prefetched so the BlockSpec
# index maps can read page ids before the DMA is issued).
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, cur_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, n_k: int, n_kv: int,
                  scale: float, window: int):
    _sweep_update(q_ref[0].astype(jnp.float32) * scale,
                  k_ref[0, 0].astype(jnp.float32),
                  v_ref[0, 0].astype(jnp.float32),
                  kpos_ref[0], cur_ref[pl.program_id(0) // n_kv], o_ref,
                  acc_ref, m_ref, l_ref, ik=pl.program_id(1), n_k=n_k,
                  window=window)


def _paged_kernel_q8(bt_ref, cur_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                     kpos_ref, o_ref, acc_ref, m_ref, l_ref, *, n_k: int,
                     n_kv: int, scale: float, window: int):
    _sweep_update(q_ref[0].astype(jnp.float32) * scale,
                  k_ref[0, 0].astype(jnp.float32),
                  v_ref[0, 0].astype(jnp.float32),
                  kpos_ref[0], cur_ref[pl.program_id(0) // n_kv], o_ref,
                  acc_ref, m_ref, l_ref, ik=pl.program_id(1), n_k=n_k,
                  window=window, k_scale=ks_ref[0, 0], v_scale=vs_ref[0, 0])


def paged_decode_attention(q, k_pool, v_pool, kpos_pool, block_tables, cur, *,
                           window: int = 0, scale: float = 0.0,
                           k_scale=None, v_scale=None,
                           interpret: bool = False):
    """Block-table-indirect decode attention over a shared page pool.

    q (B, Hq, D); k/v pools (P, Hkv, ps, D); kpos_pool (P, ps) absolute
    positions (-1 = empty); block_tables (B, nb) int32 page ids; cur (B,).
    The cache sweep walks each sequence's block table: grid step (h, j)
    DMAs page ``block_tables[b, j]`` straight from the pool — no dense
    (B, L) cache ever materializes, so HBM holds one copy of every shared
    (prefix) page. Unused table entries must point at pages whose kpos is
    -1 (the engine reserves page 0 for this). ``k_scale``/``v_scale``
    (P, Hkv, ps) enable the int8-pool path. Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    P, Hkv, ps = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    g = Hq // Hkv
    scale = scale or D ** -0.5
    grid = (B * Hkv, nb)
    quant = k_scale is not None

    def q_map(h, j, bt, cur):
        return (h, 0, 0)

    def kv_map(h, j, bt, cur):
        return (bt[h // Hkv, j], h % Hkv, 0, 0)

    in_specs = [
        pl.BlockSpec((1, g, D), q_map),
        pl.BlockSpec((1, 1, ps, D), kv_map),
        pl.BlockSpec((1, 1, ps, D), kv_map),
    ]
    operands = [q.reshape(B * Hkv, g, D), k_pool, v_pool]
    kernel = _paged_kernel_q8 if quant else _paged_kernel
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, ps), kv_map),
                     pl.BlockSpec((1, 1, 1, ps), kv_map)]
        operands += [k_scale.reshape(P, Hkv, 1, ps),
                     v_scale.reshape(P, Hkv, 1, ps)]
    in_specs.append(pl.BlockSpec(
        (1, 1, ps), lambda h, j, bt, cur: (bt[h // Hkv, j], 0, 0)))
    operands.append(kpos_pool.reshape(P, 1, ps))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, D), q_map),
        scratch_shapes=_scratch(g, D),
    )
    out = pl.pallas_call(
        functools.partial(kernel, n_k=nb, n_kv=Hkv, scale=scale,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, g, D),
                                       q.dtype if not quant else jnp.float32),
        interpret=interpret,
    )(block_tables, cur.astype(jnp.int32), *operands)
    return out.reshape(B, Hq, D).astype(q.dtype)
