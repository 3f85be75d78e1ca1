"""Seeded weights for a configuration file, made on the device in one jitted
call, in the dtype the configuration serves them in.

The weights belong to the benchmark, not to the program: the reference
(`reference.py`) makes the same tree again from the same seed, so it takes
nothing the program produced. The configuration's architecture module,
`arch/<arch>.py` (`arch`), names the leaves and their shapes and hands
the program the layout its model code reads. On a cell with a mesh the
weights are made on the mesh, each leaf split over its largest axis that
the mesh's size divides (`leaf_sharding`).
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def load_file(path: str, prefix: str, name: str):
    """The Python file at `path`, loaded as the module `<prefix>_<name>`."""
    spec = importlib.util.spec_from_file_location(
        prefix + "_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def load_arch(name: str):
    """The module `arch/<name>.py`, loaded by path."""
    path = os.path.join(HERE, "arch", name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no architecture module for arch {name!r}: {path} is missing")
    return load_file(path, "arch", name)


def arch(c: dict):
    """The architecture module of a configuration file (its `arch` key)."""
    return load_arch(c["arch"])


def frozen(c: dict) -> str:
    """A configuration as a hashable key (for caches of jitted functions);
    `json.loads` gives it back."""
    return json.dumps(c, sort_keys=True)


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any whole-number seed (64 bits and more)."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), stream])
    return jax.random.wrap_key_data(state.generate_state(2).astype(np.uint32))


def seed31(seed: int, stream: int = 0) -> int:
    """A non-negative 31-bit integer drawn from the seed (for APIs that
    take a small int seed)."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), stream])
    return int(state.generate_state(1)[0] & 0x7FFFFFFF)


def leaf_dtype(c: dict, name: str):
    # the value head is float32 in the program whatever the model dtype
    return jnp.float32 if name == "value_head" else DTYPES[c["dtype"]]


@functools.lru_cache(maxsize=None)
def _maker(spec: str, mesh=None):
    c = json.loads(spec)
    leaves = sorted(arch(c).shapes(c).items())

    def make(key):
        std = c["init_std"]
        out_std = std / np.sqrt(2 * c["num_hidden_layers"])
        tree = {}
        for i, (name, (shape, kind)) in enumerate(leaves):
            dt = leaf_dtype(c, name)
            if kind == "ones":
                tree[name] = jnp.ones(shape, dt)
            elif kind == "zeros":
                tree[name] = jnp.zeros(shape, dt)
            else:
                s = std if kind == "normal" else out_std
                z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                tree[name] = (z * s).astype(dt)
        return tree

    if mesh is None:
        return jax.jit(make)
    return jax.jit(make, out_shardings={
        name: leaf_sharding(shape, mesh) for name, (shape, _) in leaves})


def make_weights(c: dict, seed: int, mesh=None):
    """The configuration's weights from the seed: one jitted call on the
    default device, or on `mesh`, split by `leaf_sharding`."""
    return _maker(frozen(c), mesh)(seed_key(seed, 1))


def leaf_sharding(shape, mesh):
    """A leaf split over its largest axis that the mesh's size divides
    (the first of equals), over all the mesh's axes; replicated where no
    axis is divided."""
    from jax.sharding import NamedSharding, PartitionSpec
    n = mesh.devices.size
    fits = [a for a in range(len(shape)) if shape[a] % n == 0]
    if not fits:
        return NamedSharding(mesh, PartitionSpec())
    a = max(fits, key=lambda a: (shape[a], -a))
    return NamedSharding(mesh, PartitionSpec(
        *[None] * a, tuple(mesh.axis_names)))


def cell_mesh(workload: dict, chips: int):
    """The mesh a workload file asks for (`"mesh": {"shape": [...],
    "axes": [...]}`) over the first `chips` devices, or None."""
    spec = workload.get("mesh")
    if spec is None:
        return None
    from jax.sharding import AxisType
    shape, axes = tuple(spec["shape"]), tuple(spec["axes"])
    if int(np.prod(shape)) != chips:
        raise ValueError(f"mesh {shape} does not cover the cell's {chips} "
                         "chips")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:chips])
