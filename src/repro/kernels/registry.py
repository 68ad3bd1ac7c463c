"""Legal-geometry registry for the Pallas dataplane kernels.

Single source of truth for the block-size / page-size / pool-geometry
design space that the auto-tuner (``repro.tuning``) explores and the
rc3e-check kernel pass (``repro.analysis.kernelpass``) verifies. Every
knob the kernels accept is declared here with its legal range plus the
hard TPU constraints (min tile shapes, lane width, VMEM budget) that
candidates must satisfy.

Deliberately jax-free: the bare-lint analysis environment imports this
module without a jax install.
"""
from __future__ import annotations

from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Hard TPU tiling constraints (see the Pallas guide: MXU 128x128, VPU 8x128;
# min tile (sublane, lane) is dtype-dependent, lane dim always 128).
# ---------------------------------------------------------------------------
LANE = 128
SUBLANE_F32 = 8
SUBLANE_BF16 = 16
SUBLANE_INT8 = 32
VMEM_BYTES = 16 * 1024 * 1024       # per-core VMEM budget (v5e-class)

# hand-picked defaults that shipped before the tuner existed
DECODE_BLOCK_DEFAULT = 512
FLASH_BLOCK_DEFAULT = 256
MM_BLOCK_DEFAULT = 128
PAGE_SIZE_DEFAULT = 16
SLOTS_DEFAULT = 4
PREFILL_CHUNK_DEFAULT = 4

# legal ranges (the CDSE sweep axes)
DECODE_BLOCK_CHOICES: Tuple[int, ...] = (128, 256, 512, 1024, 2048)
FLASH_BLOCK_CHOICES: Tuple[int, ...] = (128, 256, 512)
MM_BLOCK_CHOICES: Tuple[int, ...] = (128, 256, 512)
PAGE_SIZE_CHOICES: Tuple[int, ...] = (8, 16, 32, 64)
SLOTS_CHOICES: Tuple[int, ...] = (2, 4, 8)
PREFILL_CHUNK_CHOICES: Tuple[int, ...] = (2, 4, 8, 16)


def sublane(dtype: str) -> int:
    if "int8" in dtype:
        return SUBLANE_INT8
    if "bfloat16" in dtype or "float16" in dtype:
        return SUBLANE_BF16
    return SUBLANE_F32


def dtype_bytes(dtype: str) -> int:
    if "int8" in dtype:
        return 1
    if "bfloat16" in dtype or "float16" in dtype:
        return 2
    if "float64" in dtype or "int64" in dtype:
        return 8
    return 4


# ---------------------------------------------------------------------------
# Divisibility rules — mirror the asserts inside the kernels themselves.
# Each returns None when legal, else a human-readable reason (the tuner
# prunes on it; the analysis pass fails on it).
# ---------------------------------------------------------------------------

def check_decode_block(cache_len: int, block_k: int) -> Optional[str]:
    """decode_attention sweeps the cache in blocks of ``min(block_k, L)``
    and requires L to divide evenly (kernels/decode_attention.py). A block
    shorter than the cache is the lane dim of the (1, 1, bk) position
    block, so it must be a multiple of the lane width."""
    if block_k < 1:
        return f"decode block_k={block_k} < 1"
    bk = min(block_k, cache_len)
    if cache_len % bk != 0:
        return f"cache_len={cache_len} not divisible by block_k={bk}"
    if bk < cache_len and bk % LANE != 0:
        return f"decode block_k={bk} not a multiple of {LANE}"
    return None


def check_flash_blocks(seq_len: int, block_q: int,
                       block_k: int) -> Optional[str]:
    """flash_attention tiles (S // bq, S // bk); both must divide S."""
    bq, bk = min(block_q, seq_len), min(block_k, seq_len)
    if seq_len % bq != 0:
        return f"seq_len={seq_len} not divisible by block_q={bq}"
    if seq_len % bk != 0:
        return f"seq_len={seq_len} not divisible by block_k={bk}"
    return None


def check_page_size(max_len: int, page_size: int) -> Optional[str]:
    """The paged pool carves max_len into whole pages; the engine asserts
    ``max_len % page_size == 0`` (runtime/serve.py)."""
    if page_size < 1:
        return f"page_size={page_size} < 1"
    if max_len % page_size != 0:
        return f"max_len={max_len} not divisible by page_size={page_size}"
    return None


def check_head_alignment(head_dim: int) -> Optional[str]:
    """Kernel layouts put head_dim on the sublane axis — keep it a multiple
    of the fp32 min sublane so blocks tile."""
    if head_dim % SUBLANE_F32 != 0:
        return f"head_dim={head_dim} not a multiple of {SUBLANE_F32}"
    return None


# ---------------------------------------------------------------------------
# VMEM footprints (bytes) — per-grid-step working sets, mirroring the
# BlockSpec + scratch shapes inside each kernel. Used for hard pruning.
# ---------------------------------------------------------------------------

def decode_vmem_bytes(block_k: int, head_dim: int, kv_dtype: str,
                      group: int) -> int:
    """decode_attention grid step for ``group`` query heads per KV head:
    q and out (g, D) fp32 + k/v blocks (bk, D) + kpos (1, bk) + int8 row
    scales (1, bk) x2 + fp32 scratch acc (g, D) and m/l (g, 1)."""
    kvb = dtype_bytes(kv_dtype)
    q_out = 2 * group * head_dim * 4
    kv = 2 * block_k * head_dim * kvb
    kpos = block_k * 4
    scales = 2 * block_k * 4 if kvb == 1 else 0
    scratch = group * head_dim * 4 + 2 * group * 4
    return q_out + kv + kpos + scales + scratch


def flash_vmem_bytes(block_q: int, block_k: int, head_dim: int,
                     dtype: str) -> int:
    """flash_attention grid step: q (bq, D) + k/v (bk, D) + acc scratch
    (bq, D) fp32 + m/l (bq,) fp32."""
    db = dtype_bytes(dtype)
    q = block_q * head_dim * db
    kv = 2 * block_k * head_dim * db
    scratch = block_q * head_dim * 4 + 2 * block_q * 4
    return q + kv + scratch


def matmul_vmem_bytes(block_m: int, block_n: int, block_k: int,
                      dtype: str) -> int:
    """stream_matmul grid step: a (bm, bk) + b (bk, bn) + fp32 acc (bm, bn)
    + out (bm, bn)."""
    db = dtype_bytes(dtype)
    return (block_m * block_k * db + block_k * block_n * db
            + block_m * block_n * (4 + db))
