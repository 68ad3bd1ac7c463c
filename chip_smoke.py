"""Smoke test of the serving path on TPU.

Drives the normal serving entry point (``repro.launch.serve``): the RC3E
hypervisor, a ``GatewayFleet``, its ``BatchingEngine``s and the prefill and
decode programs bound through the hypervisor's ``Reconfigurator``. The
model is smollm-135m at full published width (30 layers, d_model 576,
9 heads over 3 KV heads, head_dim 64, vocab 49152) with random weights from
a seed, in the config's own dtypes (bf16 activations, f32 params).

  python chip_smoke.py            # one chip: dense phase, then paged phase
  python chip_smoke.py --chips 4  # four replicas on four chips, one
                                  # cross-chip live hand-off mid-decode

Every phase checks that each request finished with its tokens, that the
hypervisor's audit log covers them, and that the logits the engines
produced for two requests (from prefill, then from every decode step) agree
with the float32 ``jax.numpy`` reference forward pass
(``repro.models.reference``). The dense phase also checks that the decode
program the fleet bound contains the Pallas decode kernel
(``tpu_custom_call``). Times printed are smoke observations, not
measurements.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed. Without a TPU the script exits 1 and prints no
result. The whole run is one process: it alone holds the chips.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "smollm-135m"
SEED = 0
N_SLOTS = 8
MAX_LEN = 2048
PAGE_SIZE = 16
MAX_NEW = 32
N_REQUESTS = 16
PROMPT_LENS = (64, 1024)          # inclusive range, drawn from the seed
FOUR_CHIP_PROMPT_LENS = (66, 96)  # one prefill bucket per chip

# Logit agreement with the float32 reference, relative to the reference
# logits' rms. The served programs compute in bf16 (unit roundoff 2**-8):
# the residual stream and every matmul output are rounded to bf16. At full
# width on 4- and 8-layer cuts (XLA-CPU, 256 positions) that costs an rms
# error of 0.015-0.016 and a largest error of 0.09; the same forward in
# fp8 activations (e4m3) costs 0.26 and 1.5, which both limits reject.
# rms(served - reference) / rms(reference), over every compared position:
REL_RMS_TOL = 0.05
# max |served - reference| / rms(reference): the tail of the same drift
# over 49152 vocab entries x every compared position:
MAX_ABS_TOL = 0.4


def _devices_or_exit(n_chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing to smoke-test", file=sys.stderr)
        sys.exit(1)
    if len(devices) < n_chips:
        print(f"chip_smoke: {n_chips} chips asked for, {len(devices)} "
              "found", file=sys.stderr)
        sys.exit(1)
    return devices


class LogitRecorder:
    """Keeps, for the tracked prompts, the logits each engine's bound
    programs produced at each position. It wraps the bound prefill and
    decode programs; the wrapped programs return exactly what the bound
    ones return, so serving is unchanged."""

    def __init__(self, model, tracked_prompts):
        import jax
        self.prompts = [tuple(int(t) for t in p) for p in tracked_prompts]
        # request index -> position -> (logits on device, chip id)
        self.logits = [dict() for _ in self.prompts]
        self._head = jax.jit(model.logits)
        self.decode_programs = {}          # chip id -> bound executable

    def _index(self, req):
        if req is None:
            return None
        key = tuple(int(t) for t in req.prompt)
        return self.prompts.index(key) if key in self.prompts else None

    def install(self, eng):
        import numpy as np
        chip = eng.device.id
        decode, prefill = eng._decode, eng._prefill
        self.decode_programs[chip] = decode

        def run_decode(params, caches, tokens, pos, *rest):
            logits, caches = decode(params, caches, tokens, pos, *rest)
            for slot, req in enumerate(eng._slots):
                i = self._index(req)
                if i is not None and slot not in eng._prefilling:
                    # a later call at the same position overwrites: the
                    # step that decodes this slot always comes last
                    self.logits[i][int(eng._pos[slot])] = \
                        (logits[slot, 0], chip)
            return logits, caches

        def run_prefill(params, toks):
            h, caches = prefill(params, toks)
            row = np.asarray(toks[0])
            for req in eng._slots:
                i = self._index(req)
                if i is None:
                    continue
                ctx = eng._ctx_tokens(req)[:-1]
                n = len(ctx)
                if n <= len(row) and np.array_equal(row[:n], ctx):
                    for p in sorted({0, n // 3, 2 * n // 3, n - 1}):
                        self.logits[i][p] = (
                            self._head(params, h[:, p:p + 1])[0, 0], chip)
            return h, caches

        eng.use_program(run_decode)
        eng._prefill = run_prefill


def compare_with_reference(cfg, params, requests, recorder):
    """Errors of the recorded logits against the f32 reference, per
    tracked request: (max |diff| / rms(ref), rms(diff) / rms(ref),
    positions compared, chips that produced them)."""
    import numpy as np
    from repro.models.reference import reference_logits
    out = []
    for i, prompt in enumerate(recorder.prompts):
        req = next(r for r in requests
                   if tuple(int(t) for t in r.prompt) == prompt)
        full = list(prompt) + list(req.out_tokens)
        ref = np.asarray(reference_logits(cfg, params, full[:-1]))
        rec = recorder.logits[i]
        assert rec, f"no logits recorded for request {req.request_id}"
        pos = sorted(rec)
        got = np.stack([np.asarray(rec[p][0], np.float32) for p in pos])
        want = ref[pos]
        scale = float(np.sqrt(np.mean(want ** 2)))
        diff = got - want
        assert np.all(np.isfinite(got)), "non-finite served logits"
        out.append({"request": req.request_id, "positions": len(pos),
                    "prompt_len": len(prompt),
                    "max_abs_rel": float(np.abs(diff).max()) / scale,
                    "rel_rms": float(np.sqrt(np.mean(diff ** 2))) / scale,
                    "chips": sorted({rec[p][1] for p in pos})})
    return out


def _check_errors(tag, errs):
    for e in errs:
        print(f"[{tag}] logits vs f32 reference, request {e['request']} "
              f"(prompt {e['prompt_len']}, {e['positions']} positions, "
              f"chips {e['chips']}): max|err|/rms {e['max_abs_rel']:.4f} "
              f"(tol {MAX_ABS_TOL}), rms err/rms {e['rel_rms']:.4f} "
              f"(tol {REL_RMS_TOL})")
        assert e["max_abs_rel"] <= MAX_ABS_TOL, (tag, e)
        assert e["rel_rms"] <= REL_RMS_TOL, (tag, e)


def _prompts(cfg, n, lens, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(lens[0], lens[1] + 1))).tolist()
            for _ in range(n)]


def _tracked(prompts):
    """The shortest and the longest prompt."""
    by_len = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    return [prompts[by_len[0]], prompts[by_len[-1]]]


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _compile_s(hv):
    return sum(e.get("compile_s", 0.0) for e in hv.log
               if e["kind"] == "fleet_up") + sum(
        e["swap_s"] for e in hv.log
        if e["kind"] == "engine_up" and not e["cache_hit"])


def phase_serve(cfg, *, paged, n_requests=N_REQUESTS, prompt_lens=PROMPT_LENS,
                max_new=MAX_NEW, n_slots=N_SLOTS, max_len=MAX_LEN,
                seed=SEED):
    """One chip: three tenants (the first on two slots) share one engine;
    seeded requests run until idle. Returns the phase's observations."""
    import jax
    from repro.launch.serve import (audit, build_fleet, open_tenants,
                                    serve_requests)
    tag = "paged" if paged else "dense"
    t0 = time.monotonic()
    hv, fleet = build_fleet(cfg, devices=1, slots=n_slots, max_len=max_len,
                            paged=paged, page_size=PAGE_SIZE, seed=seed)
    tenants = open_tenants(fleet, 3)
    prompts = _prompts(cfg, n_requests, prompt_lens, seed)
    recorder = LogitRecorder(fleet.model, _tracked(prompts))
    for eng in fleet._engines.values():
        recorder.install(eng)
    reqs = serve_requests(fleet, tenants, prompts, max_new)
    slices = audit(hv, reqs)
    wall = time.monotonic() - t0
    (program,) = recorder.decode_programs.values()
    errs = compare_with_reference(cfg, fleet.params, reqs, recorder)
    cache = hv.reconfig.cache
    device = jax.devices()[0]
    print(f"[{tag}] {device.device_kind}: {len(reqs)} requests, "
          f"{sum(len(r.out_tokens) for r in reqs)} tokens served; audit ok "
          f"({len(slices)} vSlices); decode compile "
          f"{_compile_s(hv):.1f}s; ProgramCache hits {cache.hits} misses "
          f"{cache.misses}; peak device bytes {_peak_bytes(device)}; "
          f"phase wall {wall:.1f}s (smoke observation)")
    _check_errors(tag, errs)
    fleet.close()
    return {"program_text": program.as_text(), "errors": errs}


def phase_four_chips(cfg, *, n_slots=N_SLOTS, max_len=MAX_LEN,
                     max_new=MAX_NEW, prompt_lens=FOUR_CHIP_PROMPT_LENS,
                     per_tenant=2, seed=SEED, handoff_after=4):
    """Four hypervisor devices on four chips, one tenant each; tenant-0 is
    handed off live to chip 1 mid-decode. Returns the phase's
    observations."""
    import jax
    from repro.launch.serve import audit, build_fleet
    t0 = time.monotonic()
    hv, fleet = build_fleet(cfg, devices=4, slots=n_slots, max_len=max_len,
                            seed=seed)
    dev_ids = list(hv.db.devices)
    tenants = [f"tenant-{i}" for i in range(4)]
    for i, t in enumerate(tenants):
        sess = fleet.open_session(t, slots=2)
        if fleet.device_of(t) != dev_ids[i]:
            # pack-first placement stacks tenants; spread them one per
            # device with a directed move before any traffic
            assert hv.migrate_slice(sess.slice_id, target_device=dev_ids[i],
                                    reason="placement") is not None
    chips = {dev: eng.device for dev, eng in fleet._engines.items()}
    for dev, eng in fleet._engines.items():
        on = {d for leaf in jax.tree.leaves(eng.caches)
              for d in leaf.devices()}
        assert on == {chips[dev]}, (dev, on)
    assert len(set(chips.values())) == 4, chips
    print(f"[4-chip] engines: " + ", ".join(
        f"{dev} -> chip {chips[dev].id}" for dev in sorted(chips)))

    prompts = _prompts(cfg, 4 * per_tenant, prompt_lens, seed)
    moved_prompt = prompts[0]                 # tenant-0's first request
    other = next(p for i, p in enumerate(prompts) if i % 4 == 2)
    recorder = LogitRecorder(fleet.model, [moved_prompt, other])
    for eng in fleet._engines.values():
        recorder.install(eng)
    reqs = [fleet.submit(tenants[i % 4], p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for _ in range(handoff_after):
        fleet.step()
    moved = reqs[0]
    assert 0 < len(moved.out_tokens) < max_new, "hand-off not mid-decode"
    src = fleet.device_of(tenants[0])
    new = hv.migrate_slice(fleet.session(tenants[0]).slice_id,
                           target_device=dev_ids[1], reason="handoff")
    assert new is not None and fleet.device_of(tenants[0]) == dev_ids[1]
    print(f"[4-chip] hand-off of {tenants[0]} from chip "
          f"{chips[src].id} to chip {chips[dev_ids[1]].id} after "
          f"{len(moved.out_tokens)} of {max_new} tokens")
    assert fleet.run_until_idle(), "fleet stalled with work pending"
    assert all(r.done.is_set() and len(r.out_tokens) == max_new
               for r in reqs)
    slices = audit(hv, reqs)
    errs = compare_with_reference(cfg, fleet.params, reqs, recorder)
    assert errs[0]["chips"] == sorted({chips[src].id,
                                       chips[dev_ids[1]].id}), errs[0]
    cache = hv.reconfig.cache
    print(f"[4-chip] {chips[src].device_kind}: {len(reqs)} requests, "
          f"{sum(len(r.out_tokens) for r in reqs)} tokens served; audit ok "
          f"({len(slices)} vSlices); decode compiles {cache.misses}, "
          f"ProgramCache hits {cache.hits}; peak device bytes "
          f"{[_peak_bytes(d) for d in jax.devices()[:4]]}; phase wall "
          f"{time.monotonic() - t0:.1f}s (smoke observation)")
    _check_errors("4-chip", errs)
    fleet.close()
    return {"errors": errs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-replica phase and its "
                         "reference comparison")
    args = ap.parse_args(argv)
    devices = _devices_or_exit(args.chips)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    print(f"chip_smoke: compile cache at {enable_compile_cache()}")
    cfg = get_config(ARCH)
    print(f"chip_smoke: {cfg.name} (layers {cfg.n_layers}, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim "
          f"{cfg.resolved_head_dim}, vocab {cfg.vocab_size}; dtype "
          f"{cfg.dtype}, params {cfg.param_dtype}) on "
          f"{len(devices)} x {devices[0].device_kind}")
    if args.chips == 4:
        phase_four_chips(cfg)
    else:
        dense = phase_serve(cfg, paged=False)
        assert "tpu_custom_call" in dense["program_text"], \
            "Pallas decode kernel missing from the bound decode program"
        print("[dense] bound decode program contains tpu_custom_call")
        del dense
        gc.collect()
        phase_serve(cfg, paged=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
