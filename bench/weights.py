"""Seeded random weights in the parameter layout the served program
reads. The layout itself, and which stacked entry holds a layer, come
from the configuration's architecture module (``layout``, ``layer_at``).

The program gets all of them from one jitted call on its device, in the
served dtype. The reference regenerates the same values layer by layer
(``layer``, ``top``) from the same seed, in float32: it takes nothing the
program made. Each leaf draws from its own key, derived from the seed and
the leaf's path, and each entry of a stacked leaf from that key folded
with the entry's index, so one layer can be drawn alone.
"""
from __future__ import annotations

import hashlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# path -> (shape, scale, stacked over the stage's repeats)
Layout = Dict[str, Tuple[tuple, float, bool]]


def seed_key(seed: int):
    """A key from any non-negative seed: the low and the high 32 bits are
    folded in apart, so seeds past 2**32 stay distinct."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _path_id(path: str) -> np.uint32:
    return np.uint32(int.from_bytes(
        hashlib.blake2b(path.encode(), digest_size=4).digest(), "big"))


def _draw(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _leaf(key, path, shape, scale, stacked, dtype):
    k = jax.random.fold_in(key, _path_id(path))
    if not stacked:
        return _draw(k, shape, scale, dtype)
    layer_keys = jax.vmap(lambda r: jax.random.fold_in(k, r))(
        jnp.arange(shape[0], dtype=jnp.uint32))
    return jax.vmap(lambda lk: _draw(lk, shape[1:], scale, dtype))(layer_keys)


def _tuples(node):
    """Dicts keyed '0', '1', ... (the stages, and the positions of a
    pattern stage) -> tuples, as the program's tree holds them."""
    if not isinstance(node, dict):
        return node
    node = {k: _tuples(v) for k, v in node.items()}
    if all(k.isdigit() for k in node):
        return tuple(node[str(i)] for i in range(len(node)))
    return node


def _nest(flat: dict) -> dict:
    """'stages/0/attn/wq' paths -> the program's tree."""
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return _tuples(tree)


def make_params(lay: Layout, seed: int, device, dtype=jnp.bfloat16):
    """Every leaf, on ``device``, from one jitted call."""
    def gen(key):
        return _nest({p: _leaf(key, p, s, sc, st, dtype)
                      for p, (s, sc, st) in lay.items()})
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(gen, out_shardings=sharding)(seed_key(seed))


def check_layout(lay: Layout, program_shapes) -> None:
    """Refuse a program whose parameter tree differs from this layout."""
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): tuple(leaf.shape)
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(program_shapes)[0]}
    want = {p: s for p, (s, _, _) in lay.items()}
    if flat != want:
        raise ValueError(f"the program's parameter layout {flat} is not "
                         f"the benchmark's {want}")


def _draw_f32_impl(key, shape, scale):
    return _draw(key, shape, scale, jnp.bfloat16).astype(jnp.float32)


_draw_f32 = jax.jit(_draw_f32_impl, static_argnums=(1, 2))


def layer(lay: Layout, seed: int, prefix: str, index: int) -> dict:
    """One layer's weights: entry ``index`` of the stacked leaves under
    ``prefix`` (a stage, or a position in a pattern stage: 'stages/0/'),
    float32 values of the served bfloat16 ones, keyed by the path below
    ``prefix`` ('attn/wq', 'norm1', ...)."""
    base = seed_key(seed)
    return {p[len(prefix):]: _draw_f32(
                jax.random.fold_in(jax.random.fold_in(base, _path_id(p)),
                                   np.uint32(index)), shape[1:], scale)
            for p, (shape, scale, stacked) in lay.items()
            if stacked and p.startswith(prefix)}


def top(lay: Layout, seed: int) -> dict:
    """The leaves outside the layers, float32 values of the served ones."""
    base = seed_key(seed)
    return {p: _draw_f32(jax.random.fold_in(base, _path_id(p)), s, sc)
            for p, (s, sc, st) in lay.items() if not st}
