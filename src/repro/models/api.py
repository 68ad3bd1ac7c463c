"""Family-dispatching model API + dry-run input specs.

``Model`` bundles init / forward / prefill / decode for any assigned arch.
``input_specs(cfg, shape_cell)`` returns ShapeDtypeStruct stand-ins for every
input of the corresponding step function (no device allocation) — the
dry-run and the roofline tooling lower against these.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import encdec, lm

WHISPER_DEC_LEN = 448


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---------------- init ----------------
    def init(self, key):
        if self.cfg.family == "audio":
            return encdec.init_encdec(self.cfg, key)
        return lm.init_lm(self.cfg, key)

    # ---------------- training forward ----------------
    def forward(self, params, batch, remat: bool = False):
        """batch dict -> (hidden, aux). Keys per family (see input_specs)."""
        cfg = self.cfg
        if cfg.family == "audio":
            return encdec.encdec_forward(cfg, params, batch["frames"],
                                         batch["tokens"], remat=remat)
        return lm.lm_forward(cfg, params, batch["tokens"],
                             patches=batch.get("patches"), remat=remat)

    def logits(self, params, hidden):
        if self.cfg.family == "audio":
            return encdec.encdec_logits(self.cfg, params, hidden)
        return lm.lm_logits(self.cfg, params, hidden)

    # ---------------- serving ----------------
    def prefill(self, params, batch, max_len: int, clamp_window: bool = True):
        cfg = self.cfg
        if cfg.family == "audio":
            return encdec.encdec_prefill(cfg, params, batch["frames"],
                                         batch["tokens"])
        return lm.lm_prefill(cfg, params, batch["tokens"], max_len,
                             patches=batch.get("patches"),
                             clamp_window=clamp_window)

    def decode(self, params, caches, tokens, pos):
        if self.cfg.family == "audio":
            return encdec.encdec_decode(self.cfg, params, caches, tokens, pos)
        return lm.lm_decode(self.cfg, params, caches, tokens, pos)

    def decode_paged(self, params, caches, tokens, pos, block_tables):
        """One decode step against the paged KV pool (block-table
        indirection; attention-family LMs only)."""
        if self.cfg.family == "audio":
            raise ValueError("paged decode supports decoder-only LMs")
        return lm.lm_decode_paged(self.cfg, params, caches, tokens, pos,
                                  block_tables)

    def make_caches(self, batch: int, max_len: int):
        if self.cfg.family == "audio":
            return encdec.make_encdec_caches(self.cfg, batch, max_len)
        return lm.make_decode_caches(self.cfg, batch, max_len)

    def make_paged_caches(self, n_pages: int, page_size: int,
                          n_slots: int = 0):
        """Empty paged KV pool (see ``models.stages.init_paged_cache``)."""
        if self.cfg.family == "audio":
            raise ValueError("paged caches support decoder-only LMs")
        return lm.make_paged_caches(self.cfg, n_pages, page_size, n_slots)


def get_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStruct, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def train_input_specs(cfg: ModelConfig, cell: ShapeCell):
    """Inputs of train_step: {tokens, labels[, patches | frames]}."""
    B, S = cell.global_batch, cell.seq_len
    if cfg.family == "audio":
        return {
            "frames": _sds((B, S, cfg.d_model), cfg.dtype),
            "tokens": _sds((B, WHISPER_DEC_LEN), jnp.int32),
            "labels": _sds((B, WHISPER_DEC_LEN), jnp.int32),
        }
    specs = {
        "tokens": _sds((B, S - cfg.n_patches), jnp.int32),
        "labels": _sds((B, S - cfg.n_patches), jnp.int32),
    }
    if cfg.n_patches:
        specs["patches"] = _sds((B, cfg.n_patches, cfg.d_model), cfg.dtype)
    return specs


def prefill_input_specs(cfg: ModelConfig, cell: ShapeCell):
    B, S = cell.global_batch, cell.seq_len
    if cfg.family == "audio":
        return {
            "frames": _sds((B, S, cfg.d_model), cfg.dtype),
            "tokens": _sds((B, WHISPER_DEC_LEN), jnp.int32),
        }
    specs = {"tokens": _sds((B, S - cfg.n_patches), jnp.int32)}
    if cfg.n_patches:
        specs["patches"] = _sds((B, cfg.n_patches, cfg.d_model), cfg.dtype)
    return specs


def decode_input_specs(cfg: ModelConfig, cell: ShapeCell):
    """Inputs of serve_step: one new token + caches over cell.seq_len."""
    B, S = cell.global_batch, cell.seq_len
    model = get_model(cfg)
    caches = jax.eval_shape(lambda: model.make_caches(B, S))
    return {
        "tokens": _sds((B, 1), jnp.int32),
        "pos": _sds((B,), jnp.int32),
        "caches": caches,
    }


def input_specs(cfg: ModelConfig, cell: ShapeCell):
    if cell.kind == "train":
        return train_input_specs(cfg, cell)
    if cell.kind == "prefill":
        return prefill_input_specs(cfg, cell)
    return decode_input_specs(cfg, cell)
