"""Roofline-backed analytical cost model for geometry candidates.

Grows the discipline of ``benchmarks/roofline.py`` (bytes-moved vs flops
vs the hardware ceilings, `launch/mesh.py` constants) into a per-candidate
score the design-space explorer can rank on, entirely offline:

  stream term      max(bytes moved / HBM bandwidth, flops / peak) — the
                   classic roofline bound for the decode step
  overhead term    fixed host/scalar-core cost per Pallas grid step —
                   shrinks as blocks grow (fewer steps)
  fill term        pipeline fill/imbalance cost of one block per grid row
                   (the first DMA is not overlapped) — grows with block
                   size, so the optimum tile is finite and scales with
                   device speed (fast class => bigger tiles)
  fragmentation    paged pools round each context up to whole pages:
                   bigger pages waste bandwidth, fewer pages cost more
                   grid steps — the page-size optimum is class-dependent
  slot term        parameters stream once per step regardless of batch,
                   so more slots amortize them; KV bytes stay per-slot
  chunk term       async prefill chunking: big chunks stall decode,
                   small chunks delay admission (convex in the chunk)

Hard constraints prune before scoring: VMEM fit of every kernel's
working set, HBM fit of params + KV pool, and the kernels' divisibility
rules. All pure math — no tracing, no device, deterministic across
hosts — so the benchmark JSON diffs cleanly in CI.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, MIXER_SHARED_ATTN,
                                ModelConfig)
from repro.kernels import registry as kreg
from repro.tuning.space import TunedConfig, legal_reason

# TPU v5e-class ceilings (launch/mesh.py) — scaled by device speed below.
PEAK_FLOPS = 197e12                # FLOP/s, bf16
HBM_BW = 819e9                     # bytes/s
HBM_CAP = 16 * 1024 ** 3           # bytes
HOST_OVERHEAD_S = 1e-7             # per Pallas grid step (host issue, fixed)
SLOT_HOST_S = 2e-6                 # per-slot host work per step (sampling &c)

_ATTN_KINDS = (ATTN_GLOBAL, ATTN_LOCAL, MIXER_SHARED_ATTN)


@dataclass(frozen=True)
class DeviceProfile:
    """What a device class looks like to the tuner. ``speed`` matches
    ``PhysicalDevice.speed`` (ClusterSpec.device_speeds); sub-half-speed
    classes are cut-down parts with half the VMEM and HBM."""
    name: str
    speed: float
    flops: float
    hbm_bw: float
    vmem_bytes: int
    hbm_bytes: int
    host_overhead_s: float = HOST_OVERHEAD_S


def profile_for_speed(speed: float, name: str = "") -> DeviceProfile:
    s = max(float(speed), 1e-6)
    small = s < 0.5
    return DeviceProfile(
        name=name or f"c{s:.2f}x",
        speed=s,
        flops=PEAK_FLOPS * s,
        hbm_bw=HBM_BW * s,
        vmem_bytes=kreg.VMEM_BYTES // (2 if small else 1),
        hbm_bytes=HBM_CAP // (2 if small else 1))


@dataclass
class Cost:
    """Modeled serving cost of one candidate on one device class."""
    step_s: float                  # one decode step at the candidate's slots
    us_per_token: float            # amortized service time per decoded token
    pruned: Optional[str] = None   # non-None => candidate violates a hard fit
    terms: Dict[str, float] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# Model byte/flop accounting
# ---------------------------------------------------------------------------

def _attn_layers(cfg: ModelConfig) -> int:
    return sum(1 for k in cfg.layer_kinds() if k in _ATTN_KINDS)


def kv_bytes_per_pos(cfg: ModelConfig) -> float:
    """KV-cache bytes per cached position, summed over attention layers."""
    if cfg.mla is not None:
        per = (cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim) \
            * kreg.dtype_bytes(cfg.dtype)
    else:
        per = 2 * cfg.n_kv_heads * cfg.resolved_head_dim
        if cfg.kv_quant:
            per = per * 1 + 2 * cfg.n_kv_heads * 4   # int8 + fp32 row scales
        else:
            per *= kreg.dtype_bytes(cfg.dtype)
    return float(per * _attn_layers(cfg))


def _param_bytes(cfg: ModelConfig) -> float:
    return float(cfg.param_count()) * kreg.dtype_bytes(cfg.dtype)


# ---------------------------------------------------------------------------
# Hard-constraint pruning
# ---------------------------------------------------------------------------

def prune_reason(cand: TunedConfig, cfg: ModelConfig, prof: DeviceProfile,
                 *, max_len: int, paged: bool) -> Optional[str]:
    r = legal_reason(cand, max_len=max_len, head_dim=cfg.resolved_head_dim,
                     paged=paged)
    if r is not None:
        return r
    hd = cfg.resolved_head_dim
    vmem = max(
        kreg.decode_vmem_bytes(min(cand.decode_block_k, max_len), hd,
                               "int8" if cfg.kv_quant else cfg.dtype,
                               group=cfg.n_heads // max(1, cfg.n_kv_heads)),
        kreg.flash_vmem_bytes(min(cand.flash_block_q, max_len),
                              min(cand.flash_block_k, max_len), hd,
                              cfg.dtype),
        kreg.matmul_vmem_bytes(cand.mm_block_m, cand.mm_block_n,
                               cand.mm_block_k, cfg.dtype))
    if vmem > prof.vmem_bytes:
        return f"VMEM {vmem} > {prof.vmem_bytes}"
    pool_positions = cand.n_slots * max_len
    if paged:
        # whole-page rounding wastes (ps - 1) positions worst-case per slot
        pool_positions += cand.n_slots * (cand.page_size - 1)
    hbm = _param_bytes(cfg) + pool_positions * kv_bytes_per_pos(cfg)
    if hbm > prof.hbm_bytes:
        return f"HBM {hbm / 2 ** 30:.2f}GiB > {prof.hbm_bytes / 2 ** 30:.2f}GiB"
    return None


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------

def _tiled_cost(bytes_moved: float, flops: float, grid_steps: float,
                fill_bytes: float, prof: DeviceProfile) -> float:
    stream = max(bytes_moved / prof.hbm_bw, flops / prof.flops)
    return (stream
            + grid_steps * prof.host_overhead_s
            + fill_bytes / prof.hbm_bw)


def candidate_cost(cand: TunedConfig, cfg: ModelConfig, prof: DeviceProfile,
                   *, max_len: int, paged: bool) -> Cost:
    """Score one candidate. Workload assumption (fixed, documented):
    steady-state context = max_len/2, prompts = max_len/4, and each
    request decodes max_len/2 tokens."""
    pr = prune_reason(cand, cfg, prof, max_len=max_len, paged=paged)
    if pr is not None:
        return Cost(step_s=float("inf"), us_per_token=float("inf"), pruned=pr)

    hd, ns = cfg.resolved_head_dim, cand.n_slots
    layers = _attn_layers(cfg)
    kvpp = kv_bytes_per_pos(cfg)
    avg_ctx = max(max_len // 2, 1)
    kvb = 1 if cfg.kv_quant else kreg.dtype_bytes(cfg.dtype)

    # ---- decode step: params once + KV sweep per slot -------------------
    if paged:
        ps = cand.page_size
        pages = -(-avg_ctx // ps)                     # ceil
        swept = pages * ps                            # fragmentation waste
        sweep_steps = ns * cfg.n_heads * pages * layers
        bk_fill = ps
    else:
        bk = min(cand.decode_block_k, max_len)
        swept = max_len                               # dense sweeps full L
        sweep_steps = ns * cfg.n_heads * (max_len // bk) * layers
        bk_fill = bk
    kv_bytes = ns * swept * kvpp
    fill = ns * cfg.n_heads * layers * bk_fill * 2 * hd * kvb
    dec_flops = 2.0 * cfg.param_count() * ns \
        + 4.0 * ns * avg_ctx * cfg.n_heads * hd * layers
    t_dec = _tiled_cost(_param_bytes(cfg) + kv_bytes, dec_flops,
                        sweep_steps, fill, prof) + ns * SLOT_HOST_S

    # ---- prefill (flash + matmul tiles), amortized per decoded token ----
    S = max(max_len // 4, 1)
    bq, fbk = min(cand.flash_block_q, S), min(cand.flash_block_k, S)
    flash_steps = cfg.n_heads * (-(-S // bq)) * (-(-S // fbk)) * layers
    flash_fill = cfg.n_heads * layers * (bq + fbk) * hd \
        * kreg.dtype_bytes(cfg.dtype)
    pf_flops = 2.0 * cfg.param_count() * S \
        + 4.0 * S * S * cfg.n_heads * hd * layers
    bm, bn, mbk = cand.mm_block_m, cand.mm_block_n, cand.mm_block_k
    mm_steps = (-(-S // bm)) * (-(-cfg.d_ff // bn)) \
        * (-(-cfg.d_model // mbk)) * cfg.n_layers * 3
    mm_fill = (bm * mbk + mbk * bn) * kreg.dtype_bytes(cfg.dtype) \
        * cfg.n_layers * 3
    t_prefill = _tiled_cost(
        _param_bytes(cfg) + S * kvpp, pf_flops,
        flash_steps + mm_steps, flash_fill + mm_fill, prof)

    decode_tokens = max(max_len // 2, 1)
    # ---- async prefill chunking: stall vs admission delay (convex) ------
    pc = cand.prefill_chunk
    t_chunk = (pc * t_prefill + t_dec / pc) / decode_tokens

    us_per_token = (t_dec / ns + t_prefill / decode_tokens + t_chunk) * 1e6
    return Cost(
        step_s=t_dec,
        us_per_token=us_per_token,
        terms={
            "decode_us": t_dec * 1e6,
            "prefill_us": t_prefill * 1e6,
            "chunk_us": t_chunk * 1e6,
            "kv_gb_per_step": kv_bytes / 1e9,
            "grid_steps": float(sweep_steps),
        })
