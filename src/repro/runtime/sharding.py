"""Sharding rules: map every parameter / input / cache tensor to a
PartitionSpec over the production mesh axes ("pod", "data", "model").

Strategy (baseline; the perf pass iterates on this):
  * DP: batch dims over ("pod","data") — "pod" composes with "data".
  * TP: attention (kv-)heads, ffn hidden, vocab over "model", with
    divisibility fallbacks (small-head archs replicate attention and still
    shard mlp+vocab).
  * EP: MoE expert dim over "model".
  * SP: for batch=1 long-context cells the cache sequence dim is sharded
    over "data".

Rules are name+rank based and tolerate leading stack dims inserted by the
stage planner (run/pattern stacking), by right-aligning the spec.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def shard_map(f, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def dp_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _div(n: int, mesh: Mesh, axis: str = "model") -> bool:
    return _axis_size(mesh, axis) > 1 and n % _axis_size(mesh, axis) == 0


def _right_align(spec: Tuple, rank: int) -> P:
    """Pad spec with None on the left to match leading stack dims."""
    pad = rank - len(spec)
    return P(*([None] * pad + list(spec)))


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _param_rule(cfg: ModelConfig, name: str, shape: Tuple[int, ...],
                path_names: Tuple[str, ...], mesh: Mesh) -> P:
    ms = _axis_size(mesh, "model")
    r = len(shape)

    def right(*spec):
        return _right_align(tuple(spec), r)

    if name == "tok":                      # (V, d)
        return right("model" if _div(shape[-2], mesh) else None, None)
    if name == "head":                     # (d, V)
        return right(None, "model" if _div(shape[-1], mesh) else None)

    in_moe = "moe" in path_names and name in ("wg", "wu", "wd")
    if in_moe:                             # (E, d, f) / (E, f, d)
        return right("model" if _div(shape[-3], mesh) else None, None, None)
    if name == "router":                   # (d, E) replicated (cheap, avoids
        return right(None, None)           # gathers around top_k)

    def prefer(pref_idx: int, fallback_idx: int, rank: int) -> P:
        """Shard dim ``pref_idx`` (negative) over model; if indivisible fall
        back to ``fallback_idx`` (usually the d_model dim) — never replicate
        multi-GB weights just because heads don't divide the axis."""
        spec = [None] * rank
        if _div(shape[pref_idx], mesh):
            spec[pref_idx] = "model"
        elif _div(shape[fallback_idx], mesh):
            spec[fallback_idx] = "model"
        return right(*spec)

    if name in ("wg", "wu"):               # (d, f)
        return prefer(-1, -2, 2)
    if name == "wd":                       # (f, d)
        return prefer(-2, -1, 2)

    if name == "wq":
        if "attn" in path_names and cfg.mla is not None and r >= 3:
            return prefer(-2, -3, 3)       # MLA q proj (d, h, qd)
        return prefer(-3, -4, 4)           # GQA (d, h, g, hd)
    if name in ("wk", "wv"):               # (d, h, hd)
        return prefer(-2, -3, 3)
    if name == "wo":
        if cfg.mla is not None and r >= 3 and "attn" in path_names:
            return prefer(-3, -1, 3)       # (h, v, d)
        return prefer(-4, -1, 4)           # (h, g, hd, d)
    if name in ("w_uk", "w_uv"):           # (r, h, n)
        return prefer(-2, -3, 3)
    if name == "w_dkv":                    # (d, r+rope)
        return prefer(-2, -2, 2)

    if name == "in_proj":                  # ssm (d, e)
        return prefer(-1, -2, 2)
    if name == "out_proj":                 # ssm (e, d)
        return prefer(-2, -1, 2)
    if name == "conv_w":                   # (K, C) channel-sharded
        return right(None, "model" if _div(shape[-1], mesh) else None)
    if name == "conv_b":                   # (C,)
        return right("model" if _div(shape[-1], mesh) else None)

    # norms, biases, A_log, dt_bias, D, scales: replicate
    return P(*([None] * r))


def param_specs(cfg: ModelConfig, params_shape, mesh: Mesh):
    """PartitionSpec pytree matching an ``eval_shape`` of init."""
    if cfg.tp_mode == "pure_dp":
        return jax.tree.map(lambda l: P(*([None] * l.ndim)), params_shape)
    if cfg.tp_mode == "fsdp":
        return jax.tree.map(lambda l: _fsdp_spec(l.shape, mesh), params_shape)

    def visit(path, leaf):
        names = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path)
        return _param_rule(cfg, names[-1], tuple(leaf.shape), names, mesh)
    return jax.tree_util.tree_map_with_path(visit, params_shape)


def _fsdp_spec(shape, mesh: Mesh) -> P:
    """Fully-sharded weights: shard the largest dim over the biggest axis
    combination that divides it (data×model ≫ data ≫ model), skipping the
    leading stack dim. XLA inserts the per-layer all-gather (fwd/bwd) and
    reduce-scatter (grads) — classic ZeRO-3."""
    combos = [("data", "model"), ("data",), ("model",)]
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for axes in combos:
        if not all(a in mesh.axis_names for a in axes):
            continue
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        for i in order:
            if shape[i] % n == 0 and shape[i] >= n:
                spec = [None] * len(shape)
                spec[i] = axes if len(axes) > 1 else axes[0]
                return P(*spec)
    return P(*([None] * len(shape)))


def pure_dp_axes(mesh: Mesh, batch: int):
    """Largest combination of mesh axes (data, model, pod order) whose
    product divides the batch — pure-DP mode spreads batch over all of it."""
    axes = []
    prod = 1
    for a in ("data", "model", "pod"):
        if a in mesh.axis_names and batch % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes) or None


# ---------------------------------------------------------------------------
# Input / activation / cache specs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, batch_shape, mesh: Mesh,
                batch_sharded: bool = True):
    """Inputs: shard the leading (global batch) dim over DP axes (all mesh
    axes in pure_dp mode)."""
    pure_dp = cfg.tp_mode in ("pure_dp", "fsdp")

    def visit(path, leaf):
        if leaf.ndim == 0:
            return P()
        b = leaf.shape[0]
        if not batch_sharded:
            return P(*([None] * leaf.ndim))
        if pure_dp:
            axes = pure_dp_axes(mesh, b)
            if axes is None:
                return P(*([None] * leaf.ndim))
            return P(*([axes] + [None] * (leaf.ndim - 1)))
        dp = dp_axes(mesh)
        if dp is None or b % _dp_size(mesh) != 0:
            return P(*([None] * leaf.ndim))
        return P(*([dp] + [None] * (leaf.ndim - 1)))
    return jax.tree_util.tree_map_with_path(visit, batch_shape)


def _dp_size(mesh: Mesh) -> int:
    return _axis_size(mesh, "pod") * _axis_size(mesh, "data")


def cache_specs(cfg: ModelConfig, cache_shape, mesh: Mesh,
                batch: int, seq_shard: bool = False):
    """Decode caches. Layout (stack..., B, L, heads, hd) for kv caches,
    (stack..., B, H, P, N) for ssm state. Shard B over DP when divisible;
    for batch=1 long-context, shard the cache length dim over "data"
    (sequence parallelism) and kv-heads over "model" when divisible."""
    dp = dp_axes(mesh)
    dp_ok = batch % _dp_size(mesh) == 0

    def visit(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        r = leaf.ndim
        shp = leaf.shape
        leaf_name = names[-1]
        spec = [None] * r
        # find the batch dim: first dim equal to `batch` after stack dims
        try:
            bdim = next(i for i, s in enumerate(shp) if s == batch)
        except StopIteration:
            return P(*spec)
        if dp_ok and dp is not None:
            spec[bdim] = dp
        if leaf_name in ("k", "v", "c_kv", "k_rope", "pos", "cross_k",
                         "cross_v", "k_scale", "v_scale"):
            ldim = bdim + 1                     # cache length dim
            if ldim < r:
                if seq_shard and not dp_ok and _div(shp[ldim], mesh, "data"):
                    spec[ldim] = "data"
                # kv heads dim (k/v only): (B, L, h, hd); when heads don't
                # divide the model axis, shard the cache LENGTH over model
                # instead — a replicated 32k cache is tens of GB/device
                if leaf_name in ("k", "v", "cross_k", "cross_v", "k_scale",
                                 "v_scale") \
                        and ldim + 1 < r and _div(shp[ldim + 1], mesh):
                    spec[ldim + 1] = "model"
                elif spec[ldim] is None and _div(shp[ldim], mesh):
                    spec[ldim] = "model"
        if leaf_name == "state":                 # ssm (B, H, P, N)
            if bdim + 1 < r and _div(shp[bdim + 1], mesh):
                spec[bdim + 1] = "model"
        if leaf_name == "conv":                  # (B, K, C)
            if bdim + 2 < r and _div(shp[bdim + 2], mesh):
                spec[bdim + 2] = "model"
        return P(*spec)
    return jax.tree_util.tree_map_with_path(visit, cache_shape)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def zero1_specs(cfg: ModelConfig, pspecs, params_shape, mesh: Mesh):
    """Optimizer-state sharding (ZeRO-1): take each param's spec and
    additionally shard the first unsharded, data-divisible dim over "data".
    XLA inserts the reduce-scatter/all-gather pair around the update."""
    ds = _axis_size(mesh, "data")

    def one(spec: P, shape):
        if ds <= 1:
            return spec
        parts = list(spec) + [None] * (len(shape.shape) - len(spec))
        used = set()
        for p in parts:
            for a in (p if isinstance(p, tuple) else (p,)):
                if a:
                    used.add(a)
        if "data" in used:        # already data-sharded (e.g. FSDP specs)
            return P(*parts)
        for i, (dim, p) in enumerate(zip(shape.shape, parts)):
            if p is None and dim % ds == 0 and dim >= ds:
                parts[i] = "data"
                break
        return P(*parts)

    return jax.tree.map(one, pspecs, params_shape,
                        is_leaf=lambda x: isinstance(x, P))
