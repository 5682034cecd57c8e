"""Production mesh construction.

A function — not a module-level constant — so importing never touches JAX
device state. The dry-run process sets XLA_FLAGS for 512 host devices before
any JAX import; tests and benches see the real (1-device) platform.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(axes: Tuple[str, ...] = ("data",),
                    devices: Optional[Sequence] = None) -> Mesh:
    """Mesh over ``devices`` (default: every local device), all of them on
    the first axis. Passing ``jax.devices()[i:i + 1]`` pins an engine or a
    trainer to one chip."""
    devices = list(jax.devices() if devices is None else devices)
    shape = (len(devices),) + (1,) * (len(axes) - 1)
    return make_mesh(shape, axes, devices=devices)


def describe(mesh: Mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())


def n_chips(mesh: Mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
