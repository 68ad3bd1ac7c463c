import os
# a CPU tool: 512 virtual host devices, and never the TPU (so neither the
# dry-run nor launch/sweep.py's children take a chip another process holds)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, prove memory fits, and extract roofline terms.

MUST be run as its own process (`python -m repro.launch.dryrun ...`) so the
XLA_FLAGS above take effect before jax initializes.

Per cell this prints/saves:
  - compiled.memory_analysis()  (per-device bytes: proof it fits)
  - compiled.cost_analysis()    (XLA's aggregate — loop-UNDERCOUNTED, kept
                                 for reference)
  - loop-corrected per-device flops / dot-bytes / collective wire bytes from
    repro.launch.hlo_analysis
  - three-term roofline + dominant bottleneck + MODEL_FLOPS ratio
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import LONG_CONTEXT_ARCHS, SHAPES, get_config
from repro.configs.base import ShapeCell
from repro.launch.hlo_analysis import analyze_hlo, cpu_dus_legalization_bytes
from repro.launch.mesh import (HBM_BYTES_S, ICI_BYTES_S, PEAK_FLOPS_BF16,
                               chips, make_production_mesh)
from repro.models.api import (WHISPER_DEC_LEN, get_model, input_specs)
from repro.optim.adamw import AdamWConfig
from repro.runtime.serve import jit_serve_step
from repro.runtime.sharding import (batch_specs, named, param_specs,
                                    zero1_specs)
from repro.runtime.train import TrainOpts, init_train_state, make_train_step

# Cells skipped with a documented reason (DESIGN.md §4)
SKIPS = {
    ("long_500k", arch): "full-attention cache at 500k infeasible by design"
    for arch in ("phi3-mini-3.8b", "smollm-135m", "deepseek-v2-lite-16b",
                 "qwen3-moe-30b-a3b", "llava-next-34b", "whisper-tiny")
}


def dryrun_cfg(arch: str, dp_total: int = 16, tp: int = 16,
               cell_kind: str = "train"):
    """Dry-run flavor: bf16 params+compute (production numerics); MoE
    dispatch made local to the mesh's data-parallel extent; attention TP
    switches to query-seq sharding on train cells when kv heads don't
    divide the model axis (the score einsum would otherwise replicate)."""
    cfg = get_config(arch).replace(dtype="bfloat16", param_dtype="bfloat16")
    if cfg.moe is not None:
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  dp_shards=dp_total))
    # sub-GB models: the whole mesh is better used as pure DP (weights
    # replicated, one grad all-reduce) than as 16-way TP of tiny matmuls
    if cell_kind == "train" and cfg.param_count() * 2 <= 800e6:
        return cfg.replace(tp_mode="pure_dp", attn_tp="none")
    # NOTE: tp_mode="fsdp" exists but is NOT the default — measured on
    # gemma2/llava/zamba2, GSPMD re-gathers the full scan-stacked weights
    # every layer iteration (283-673 s of wire vs 9.6-29 s for Megatron-SP).
    # Proper ZeRO-3 needs per-layer gather scheduling that scan+GSPMD does
    # not express; recorded as a refuted hypothesis in EXPERIMENTS.md §Perf.
    if (cell_kind == "train" and cfg.mla is None
            and cfg.n_kv_heads % tp != 0):
        cfg = cfg.replace(attn_tp="seq")
    # int8 KV cache for decode cells (optimized variant; RC3E_KV_QUANT=1)
    if (cell_kind == "decode" and cfg.mla is None
            and os.environ.get("RC3E_KV_QUANT") == "1"):
        cfg = cfg.replace(kv_quant=True)
    return cfg


def _train_lowerable(model, mesh, cell: ShapeCell):
    cfg = model.cfg
    opts = TrainOpts(remat=True, loss_chunk=512)
    state_shape = jax.eval_shape(
        lambda: init_train_state(model, jax.random.key(0), opts))
    batch_shape = input_specs(cfg, cell)
    pspecs = param_specs(cfg, state_shape["params"], mesh)
    ospecs = zero1_specs(cfg, pspecs, state_shape["params"], mesh)
    state_specs = {
        "params": pspecs,
        "opt_state": {"mu": ospecs, "nu": ospecs,
                      "count": jax.sharding.PartitionSpec()},
        "step": jax.sharding.PartitionSpec(),
    }
    bspecs = batch_specs(cfg, batch_shape, mesh)
    step = make_train_step(model, opts, grad_specs=ospecs)
    jitted = jax.jit(step,
                     in_shardings=(named(mesh, state_specs),
                                   named(mesh, bspecs)),
                     donate_argnums=(0,))
    return jitted, (state_shape, batch_shape)


def _prefill_lowerable(model, mesh, cell: ShapeCell):
    from repro.runtime.sharding import cache_specs
    cfg = model.cfg
    params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    batch_shape = input_specs(cfg, cell)
    pspecs = param_specs(cfg, params_shape, mesh)
    bspecs = batch_specs(cfg, batch_shape, mesh)

    def prefill_step(params, batch):
        return model.prefill(params, batch, cell.seq_len)

    # pin the produced caches to the decode-cell sharding (otherwise XLA
    # may leave multi-GB caches replicated across the model axis)
    cshape = jax.eval_shape(
        lambda: model.make_caches(cell.global_batch, cell.seq_len))
    cspecs = cache_specs(cfg, cshape, mesh, cell.global_batch)
    dp = None
    h_spec = jax.sharding.PartitionSpec()
    from repro.runtime.sharding import dp_axes
    dp = dp_axes(mesh)
    if cell.global_batch % (chips(mesh) // mesh.shape["model"]) == 0:
        h_spec = jax.sharding.PartitionSpec(dp, None, None)
    jitted = jax.jit(prefill_step,
                     in_shardings=(named(mesh, pspecs),
                                   named(mesh, bspecs)),
                     out_shardings=(
                         jax.sharding.NamedSharding(mesh, h_spec),
                         named(mesh, cspecs)))
    return jitted, (params_shape, batch_shape)


def _decode_lowerable(model, mesh, cell: ShapeCell):
    cfg = model.cfg
    params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    specs = input_specs(cfg, cell)
    jitted, _ = jit_serve_step(model, mesh, cell.global_batch, cell.seq_len,
                               params_shape, specs["caches"])
    return jitted, (params_shape, specs["caches"], specs["tokens"],
                    specs["pos"])


def model_flops(cfg, cell: ShapeCell) -> float:
    """6·N_active·D for train, 2·N_active·D forward-only."""
    n = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * cell.global_batch        # one token per sequence


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             keep_hlo: bool = False) -> dict:
    cell = SHAPES[shape]
    reason = SKIPS.get((shape, arch))
    if reason:
        return {"arch": arch, "shape": shape, "skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = chips(mesh)
    dp_total = n_chips // mesh.shape["model"]
    cfg = dryrun_cfg(arch, dp_total=dp_total, tp=mesh.shape["model"],
                     cell_kind=cell.kind)
    model = get_model(cfg)

    t0 = time.time()
    if cell.kind == "train":
        jitted, args = _train_lowerable(model, mesh, cell)
    elif cell.kind == "prefill":
        jitted, args = _prefill_lowerable(model, mesh, cell)
    else:
        jitted, args = _decode_lowerable(model, mesh, cell)

    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    costs = analyze_hlo(hlo, n_chips)

    arg_b = getattr(ma, "argument_size_in_bytes", 0)
    out_b = getattr(ma, "output_size_in_bytes", 0)
    tmp_b = getattr(ma, "temp_size_in_bytes", 0)
    alias_b = getattr(ma, "alias_size_in_bytes", 0)
    peak_b = arg_b + out_b + tmp_b - alias_b
    # XLA-CPU legalizes bf16 dynamic-update-slice through f32 copies of the
    # whole residual stack (TPU has native bf16 DUS) — project those out.
    legal_b = cpu_dus_legalization_bytes(hlo)
    # detected stacks may share one allocation across sequential loops, so
    # bound the correction: never project below arguments+outputs
    tpu_peak_b = max(arg_b + out_b, peak_b - legal_b)

    t_compute = costs.flops / PEAK_FLOPS_BF16
    t_memory = costs.dot_bytes / HBM_BYTES_S
    # with the Pallas flash-attention kernel, score/prob matrices stay in
    # VMEM — subtract their HBM traffic (kernel validated in tests/)
    t_memory_flash = (costs.dot_bytes - costs.score_bytes) / HBM_BYTES_S
    t_coll = costs.collective_bytes / ICI_BYTES_S
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, cell)
    hlo_flops_global = costs.flops * n_chips

    result = {
        "arch": arch, "shape": shape,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "per_device_bytes": int(peak_b),
            "arguments": int(arg_b), "outputs": int(out_b),
            "temps": int(tmp_b), "aliased": int(alias_b),
            "cpu_dus_legalization_bytes": int(legal_b),
            "projected_tpu_bytes": int(tpu_peak_b),
            "fits_16GB": bool(tpu_peak_b < 16e9),
        },
        "xla_cost_analysis": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "note": "loop bodies counted once (verified undercount)",
        },
        "per_device": {
            "flops": costs.flops,
            "dot_bytes": costs.dot_bytes,
            "collective_wire_bytes": costs.collective_bytes,
            "collective_breakdown": dict(costs.collectives),
            "collective_ops": costs.collective_count,
        },
        "roofline": {
            "compute_s": t_compute, "memory_s": t_memory,
            "memory_s_flash_kernel": t_memory_flash,
            "score_bytes": costs.score_bytes,
            "collective_s": t_coll, "dominant": dominant,
            "model_flops_global": mf,
            "hlo_flops_global": hlo_flops_global,
            "useful_flops_ratio": mf / hlo_flops_global
            if hlo_flops_global else 0.0,
            "step_time_bound_s": max(terms.values()),
            "roofline_fraction": t_compute / max(terms.values())
            if max(terms.values()) > 0 else 0.0,
        },
    }
    if keep_hlo:
        result["hlo_path"] = _save_hlo(arch, shape, result["mesh"], hlo)
    return result


def _save_hlo(arch, shape, mesh_name, hlo) -> str:
    d = os.path.join("results", "hlo")
    os.makedirs(d, exist_ok=True)
    p = os.path.join(d, f"{arch}_{shape}_{mesh_name}.hlo.txt")
    with open(p, "w") as f:
        f.write(hlo)
    return p


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--mesh", default="single", choices=("single", "multi"))
    ap.add_argument("--json", default=None, help="write result JSON here")
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args()

    res = run_cell(args.arch, args.shape, multi_pod=(args.mesh == "multi"),
                   keep_hlo=args.keep_hlo)
    text = json.dumps(res, indent=1)
    print(text)
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
