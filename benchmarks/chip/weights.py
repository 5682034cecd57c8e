"""Weights from the seed, made on the device in one jitted call.

The layout comes from the model family's file (``families/<family>.py``:
``layout(m)``, each leaf's path, shape, dtype and init kind, and
``init_stds(m, init)``), written out from the configuration's widths, so the
reference can be handed the same values without asking the program for
anything; the harness checks that the layout equals the program's own
parameter specs before it serves or trains.
"""
from __future__ import annotations

from types import ModuleType
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

_DT = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any whole seed (64 bits), one per stream."""
    s = int(seed) & (2 ** 64 - 1)
    data = np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(data), stream)


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def check_layout(family: ModuleType, m: Dict[str, Any],
                 program_specs: Any) -> None:
    """Raise unless the program's parameter specs have exactly the family's
    layout."""
    got = {}
    leaves = jax.tree_util.tree_flatten_with_path(
        program_specs, is_leaf=lambda x: hasattr(x, "logical_axes"))[0]
    for path, s in leaves:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        got[key] = (tuple(s.shape), jnp.dtype(s.dtype).name)
    want = {k: (tuple(v[0]), jnp.dtype(_DT[v[1]]).name)
            for k, v in family.layout(m).items()}
    if got != want:
        raise ValueError(f"parameter layout differs from the program's: "
                         f"benchmark {want} program {got}")


def make_init(family: ModuleType, m: Dict[str, Any], init: Dict[str, Any],
              out_shardings: Optional[Any] = None):
    """jit(key -> params) building every leaf of the family's layout in one
    program: leaf i of the sorted paths is ones, or a normal from the key
    folded with i, at its init kind's std."""
    stds = family.init_stds(m, init)
    lay = family.layout(m)

    def build(key):
        flat = {}
        for i, (path, (shape, dt, kind)) in enumerate(sorted(lay.items())):
            if kind == "ones":
                flat[path] = jnp.ones(shape, _DT[dt])
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * stds[kind]
            flat[path] = x.astype(_DT[dt])
        return nest(flat)

    return jax.jit(build, out_shardings=out_shardings)

