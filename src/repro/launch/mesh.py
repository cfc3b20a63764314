"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, names):
    """``jax.make_mesh`` with Auto axes: the launchers' sharding rules are
    GSPMD propagation hints, and explicit axes (the make_mesh default)
    would demand an output sharding on every ambiguous op instead."""
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False, expert: bool = False):
    """Single pod: (16, 16) over ("data", "model") — 256 chips (v5e pod).
    Multi-pod: (2, 16, 16) over ("pod", "data", "model") — 512 chips; the
    ``pod`` axis composes with ``data`` for batch sharding (DCN-friendly:
    only data-parallel gradient reductions cross pods). ``expert=True``
    splits the model axis into ("model", "expert"): expert-parallel MoE
    dispatch (all-to-all over "expert") composes with tensor parallelism on
    the remaining "model" axis at the same chip count."""
    if multi_pod:
        if expert:
            return _auto_mesh((2, 16, 4, 4),
                              ("pod", "data", "model", "expert"))
        return _auto_mesh((2, 16, 16), ("pod", "data", "model"))
    if expert:
        return _auto_mesh((16, 4, 4), ("data", "model", "expert"))
    return _auto_mesh((16, 16), ("data", "model"))


def make_debug_mesh(*, multi_pod: bool = False, expert: bool = False):
    """Reduced mesh for CI smoke tests (needs only 8/16 host devices)."""
    if multi_pod:
        if expert:
            return _auto_mesh((2, 2, 2, 2), ("pod", "data", "model", "expert"))
        return _auto_mesh((2, 2, 4), ("pod", "data", "model"))
    if expert:
        return _auto_mesh((2, 2, 2), ("data", "model", "expert"))
    return _auto_mesh((2, 4), ("data", "model"))


def make_expert_mesh(n_devices: int | None = None):
    """1-D ("expert",) serving mesh over the first ``n_devices`` host
    devices — the expert-parallel axis of the sharded serving path. Unlike
    the training meshes this does not require every available device: a
    4-way forced-host CPU process can still serve D=2."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    d = len(devs) if n_devices is None else int(n_devices)
    if d < 1 or d > len(devs):
        raise ValueError(
            f"make_expert_mesh: need 1 <= n_devices <= {len(devs)} "
            f"available devices, got {n_devices}")
    return Mesh(np.asarray(devs[:d]), ("expert",))


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
