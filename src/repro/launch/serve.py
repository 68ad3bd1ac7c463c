"""Serving launcher: stands up the multi-tenant serving FLEET for an arch
and runs a synthetic request workload from several tenants through the RC3E
hypervisor — every request is admitted, bound to a vSlice, batched across
tenants on its vSlice's device, and logged by the hypervisor. With
``--devices N`` the fleet runs one engine per physical device and the
DeviceDB's placement decides where each tenant decodes.

The model is served in its config's own dtypes. ``chip_smoke.py`` drives
the same functions (``build_fleet``, ``open_tenants``, ``serve_requests``,
``audit``) at full width on the chip.

Example (CPU-runnable):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduce \
      --requests 12 --devices 2
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import ModelConfig
from repro.core import MAX_SLOTS, ClusterSpec, Hypervisor
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.rc2f import AdmissionError
from repro.runtime import GatewayFleet
from repro.runtime.serve import Request


def build_fleet(cfg: ModelConfig, *, devices: int, slots: int, max_len: int,
                paged: bool = False, page_size: int = 16,
                seed: int = 0) -> Tuple[Hypervisor, GatewayFleet]:
    """Seeded random weights for ``cfg``, a one-node inventory of
    ``devices`` hypervisor devices, and the fleet serving the model."""
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=devices))
    fleet = GatewayFleet(hv, model, params, n_slots=slots, max_len=max_len,
                         paged=paged, page_size=page_size)
    return hv, fleet


def open_tenants(fleet: GatewayFleet, n: int) -> List[str]:
    """Open ``n`` tenant sessions: the first on a 2-slot vSlice, the rest
    on 1 slot each."""
    tenants = [f"tenant-{i}" for i in range(n)]
    for i, t in enumerate(tenants):
        sess = fleet.open_session(t, slots=2 if i == 0 else 1)
        print(f"{t}: session on {sess.slice_id} "
              f"({sess.slots} slot(s), {fleet.device_of(t)})")
    return tenants


def serve_requests(fleet: GatewayFleet, tenants: Sequence[str],
                   prompts: Sequence[Sequence[int]],
                   max_new: int) -> List[Request]:
    """Submit one request per prompt, round-robin over ``tenants``, and
    run the fleet until idle. Returns the requests, all finished."""
    def submit_throttled(tenant, prompt):
        """Back-pressure instead of failing when a tenant hits its
        in-flight quota: drive the fleet until the backlog drains."""
        while True:
            try:
                return fleet.submit(tenant, prompt, max_new_tokens=max_new)
            except AdmissionError:
                if fleet.step() == 0:
                    raise       # nothing draining: structurally rejected
    reqs = [submit_throttled(tenants[i % len(tenants)], p)
            for i, p in enumerate(prompts)]
    assert fleet.run_until_idle(), "fleet stalled with work pending"
    unfinished = [r.request_id for r in reqs
                  if not r.done.is_set() or not r.out_tokens]
    assert not unfinished, f"requests {unfinished} finished without tokens"
    return reqs


def audit(hv: Hypervisor, reqs: Sequence[Request]) -> List[str]:
    """Every request must have been served through a hypervisor vSlice.
    Returns the slices they were logged against."""
    serve_events = {e["request"]: e for e in hv.log if e["kind"] == "serve"}
    missing = [r.request_id for r in reqs
               if r.request_id not in serve_events]
    assert not missing, f"requests {missing} missing from hv.log"
    assert all(e["slice"].startswith("vs-") for e in serve_events.values())
    return sorted({e["slice"] for e in serve_events.values()})


def main(argv: Optional[Sequence[str]] = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--devices", type=int, default=0,
                    help="physical devices in the inventory "
                         "(0 = size to the tenant count)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache pool engines (block tables, "
                         "per-tenant page budgets, COW prefix sharing)")
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)

    # size the simulated inventory to the tenant count unless --devices set:
    # first tenant gets a 2-slot vSlice, the rest 1 slot each
    total_slots = args.tenants + 1
    n_devices = args.devices or max(1, -(-total_slots // MAX_SLOTS))
    hv, fleet = build_fleet(cfg, devices=n_devices, slots=args.slots,
                            max_len=args.max_len, paged=args.paged,
                            page_size=args.page_size)
    tenants = open_tenants(fleet, args.tenants)
    print(f"{cfg.name} fleet up: {len(fleet._engines)} engine(s) across "
          f"{n_devices} device(s), {args.slots} decode slots each, "
          f"{len(tenants)} tenants")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=rng.integers(2, 9)).tolist()
               for _ in range(args.requests)]
    t0 = time.monotonic()
    reqs = serve_requests(fleet, tenants, prompts, args.max_new)
    wall = time.monotonic() - t0

    total = sum(len(r.out_tokens) for r in reqs)
    lat = [(r.finished_at - r.submitted_at) for r in reqs]
    print(f"\n{len(reqs)} requests, {total} tokens, {wall:.2f}s wall "
          f"on {jax.devices()[0].device_kind} ({total/wall:.1f} tok/s), "
          f"median latency {np.median(lat)*1e3:.0f} ms")
    if args.paged:
        for dev, fs in sorted(fleet.fleet_stats().items()):
            if "pages" in fs:
                print(f"  {dev} pages: {fs['pages']}")
    for t, s in sorted(fleet.stats().items()):
        print(f"  {t}: {s['served']} served on {s['slice']} "
              f"({s['device']}), {s['tokens_out']} tokens, "
              f"quota {s['quota']}")

    slices = audit(hv, reqs)
    print(f"\naudit: all {len(reqs)} requests logged against "
          f"hypervisor vSlices ({slices})")
    fleet.close()


if __name__ == "__main__":
    main()
