"""Device placement of the serving dataplane: each fleet engine's params,
caches and bound decode executable live on the JAX device backing its
hypervisor device, and the ProgramCache keys programs by placement. (The
cross-device hand-off itself runs on four virtual devices in
``test_chip_smoke.py``.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, reduced
from repro.configs.base import GeometryConfig
from repro.core import ClusterSpec, Hypervisor, ProgramCache
from repro.models import get_model
from repro.runtime import GatewayFleet
from repro.runtime.serve import BatchingEngine


@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(get_config("smollm-135m")).replace(dtype="float32")
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _placed(x, device):
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=SingleDeviceSharding(device))


def test_program_cache_key_includes_placement():
    cache = ProgramCache()
    x = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    dev = jax.devices()[0]
    unplaced = cache.key("fp", (x,))
    placed = cache.key("fp", (_placed(x, dev),))
    assert unplaced[:3] == placed[:3]
    assert unplaced != placed
    assert placed[3] == str(dev.id)
    assert cache.key("fp", (_placed(x, dev),)) == placed


def test_fleet_maps_inventory_devices_onto_jax_devices(served_model):
    model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=2, devices_per_node=3))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=32)
    n = len(jax.devices())
    for i, dev in enumerate(hv.db.devices):
        assert fleet.jax_device(dev) == jax.devices()[i % n]
    fleet.close()


@pytest.mark.parametrize("paged", [False, True])
def test_engine_state_lives_on_its_device(served_model, paged):
    model, params = served_model
    dev = jax.devices()[0]
    eng = BatchingEngine(model, params, n_slots=2, max_len=32, paged=paged,
                         page_size=8, device=dev)
    for leaf in jax.tree.leaves((eng.params, eng.caches)):
        assert leaf.committed and leaf.devices() == {dev}
    eng.submit(np.arange(1, 7), max_new_tokens=3)
    assert eng.run_until_idle()
    for leaf in jax.tree.leaves(eng.caches):
        assert leaf.devices() == {dev}


def test_fleet_binds_executables_compiled_for_the_engine_device(
        served_model):
    model, params = served_model
    hv = Hypervisor(ClusterSpec(n_nodes=1, devices_per_node=2))
    fleet = GatewayFleet(hv, model, params, n_slots=2, max_len=32)
    fleet.open_session("a", slots=4, service_model="rsaas")
    fleet.open_session("b", slots=4, service_model="rsaas")
    keys = list(hv.reconfig.cache._entries)
    assert keys and all(k[3] == str(jax.devices()[0].id) for k in keys)
    fleet.submit("a", [1, 2, 3, 4, 5], max_new_tokens=2)
    assert fleet.run_until_idle()
    fleet.close()


def test_decode_kernel_refuses_untileable_cache_instead_of_falling_back(
        served_model):
    """A cache the decode kernel cannot sweep raises the registry's reason
    rather than silently taking the XLA path."""
    model, params = served_model
    bad = get_model(model.cfg.replace(geometry=GeometryConfig(
        decode_block_k=48, kernel_force="interpret")))
    caches = bad.make_caches(1, 96)
    with pytest.raises(ValueError, match="not a multiple of 128"):
        bad.decode(params, caches, jnp.zeros((1, 1), jnp.int32),
                   jnp.zeros((1,), jnp.int32))
