"""Stands up the system under test from a configuration file: the RC3E
hypervisor with one node of ``devices`` devices, the paged
``GatewayFleet`` serving the model, and one serving session per tenant.

The same steps as ``repro.launch.serve.build_fleet``/``open_tenants``,
with the pool size and each tenant's service model taken from the file,
and the model from the configuration's architecture module (``program``).
"""
from __future__ import annotations

from typing import List, Tuple

import jax

def build(cfg, dep: dict, params) -> Tuple[object, object, List[str]]:
    """(hypervisor, fleet, tenant names) serving the program's
    ``ModelConfig`` ``cfg``. Fails unless every device of the deployment
    got its own engine on its own chip."""
    from repro.core import ClusterSpec, Hypervisor
    from repro.models import get_model
    from repro.runtime import GatewayFleet
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=dep["devices"]))
    fleet = GatewayFleet(hv, get_model(cfg), params,
                         n_slots=dep["n_slots"], max_len=dep["max_len"],
                         paged=True, page_size=dep["page_size"],
                         cache_pages=dep.get("cache_pages"))
    tenants = []
    for i, t in enumerate(dep["tenants"]):
        name = f"tenant-{i}"
        fleet.open_session(name, slots=t["slots"], service_model=t["service"])
        tenants.append(name)
    engines = {fleet.device_of(t) for t in tenants}
    chips = {fleet.jax_device(d).id for d in engines}
    if len(engines) != dep["devices"] or len(chips) != dep["devices"]:
        raise RuntimeError(f"placement put the tenants on {sorted(engines)} "
                           f"(chips {sorted(chips)}); the deployment "
                           f"names {dep['devices']} devices")
    return hv, fleet, tenants


def chips_of(fleet, tenants: List[str]) -> List[jax.Device]:
    return sorted({fleet.jax_device(fleet.device_of(t)) for t in tenants},
                  key=lambda d: d.id)
