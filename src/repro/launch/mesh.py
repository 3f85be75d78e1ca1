"""Production mesh builders.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """v5e pod mesh: 16x16 = 256 chips per pod; 2 pods for multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape))


def make_host_mesh() -> Mesh:
    """Whatever devices this host actually has, as a 1D data mesh (used by
    smoke tests / the CPU RL driver)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), (AxisType.Auto,))


def engine_submeshes(mesh: Mesh, n_engines: int,
                     axis_name: str = "model") -> list:
    """Split a mesh's devices into `n_engines` disjoint 1D submeshes —
    the per-engine device sets of the real-mesh actor pool (DESIGN.md
    §11). Each engine places its params/cache on its own submesh; the
    trainer→engine weight transfer is then a cross-mesh reshard, executed
    by `launch.meshrt.MeshBroadcastExecutor`. Devices must split evenly."""
    import numpy as np

    devices = mesh.devices.reshape(-1)
    n = int(n_engines)
    if n <= 0 or len(devices) % n:
        raise ValueError(
            f"cannot split {len(devices)} devices into {n} engine meshes")
    per = len(devices) // n
    return [Mesh(np.asarray(devices[i * per:(i + 1) * per]), (axis_name,))
            for i in range(n)]


def make_disaggregated_meshes(mesh: Mesh, n_train_model: int = 8):
    """PipelineRL resource split: T trainer chips vs N-T generator chips.

    Splits the trailing "model" axis of the production mesh into a trainer
    submesh and a generator submesh (the paper's T-vs-(N-T) knob mapped to a
    mesh partition). Used by the launcher to place train_step and decode_step
    on disjoint device sets; the in-flight weight update is the reshard
    between the two.
    """
    devices = mesh.devices
    model_ax = mesh.axis_names.index("model")
    n_model = devices.shape[model_ax]
    if not (0 < n_train_model < n_model):
        raise ValueError(f"n_train_model must be in (0, {n_model})")
    take = [slice(None)] * devices.ndim
    take[model_ax] = slice(0, n_train_model)
    train_dev = devices[tuple(take)]
    take[model_ax] = slice(n_train_model, None)
    gen_dev = devices[tuple(take)]
    return Mesh(train_dev, mesh.axis_names), Mesh(gen_dev, mesh.axis_names)
