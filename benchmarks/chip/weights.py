"""Weights from the seed, made on the device in one jitted call.

The layout is written out here from the configuration's widths (the dense
block's leaves, stacked over layers), so the reference can be handed the same
values without asking the program for anything; the harness checks that the
layout equals the program's own parameter specs before it serves or trains.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

_DT = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# leaves whose output feeds the residual stream: their init is scaled down
_OUT_PROJ = ("w_o", "wo")


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any whole seed (64 bits), one per stream."""
    s = int(seed) & (2 ** 64 - 1)
    data = np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(data), stream)


def layout(m: Dict[str, Any]) -> Dict[str, Any]:
    """{path: (shape, dtype name, init kind)} of the dense LM's parameters."""
    L, d, f, V = m["num_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    H, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    w = m["param_dtype"]
    out = {
        "embed": ((V, d), w, "normal"),
        "final_norm": ((d,), "float32", "ones"),
        "blocks/ln1": ((L, d), "float32", "ones"),
        "blocks/ln2": ((L, d), "float32", "ones"),
        "blocks/attn/w_q": ((L, d, H, D), w, "normal"),
        "blocks/attn/w_k": ((L, d, Hkv, D), w, "normal"),
        "blocks/attn/w_v": ((L, d, Hkv, D), w, "normal"),
        "blocks/attn/w_o": ((L, H, D, d), w, "normal"),
        "blocks/mlp/wi_gate": ((L, d, f), w, "normal"),
        "blocks/mlp/wi_up": ((L, d, f), w, "normal"),
        "blocks/mlp/wo": ((L, f, d), w, "normal"),
    }
    if not m.get("tie_embeddings", False):
        out["head"] = ((d, V), w, "normal")
    return out


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


def check_layout(m: Dict[str, Any], program_specs: Any) -> None:
    """Raise unless the program's parameter specs have exactly this layout."""
    got = {}
    leaves = jax.tree_util.tree_flatten_with_path(
        program_specs, is_leaf=lambda x: hasattr(x, "logical_axes"))[0]
    for path, s in leaves:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        got[key] = (tuple(s.shape), jnp.dtype(s.dtype).name)
    want = {k: (tuple(v[0]), jnp.dtype(_DT[v[1]]).name)
            for k, v in layout(m).items()}
    if got != want:
        raise ValueError(f"parameter layout differs from the program's: "
                         f"benchmark {want} program {got}")


def make_init(m: Dict[str, Any], init: Dict[str, Any],
              out_shardings: Optional[Any] = None):
    """jit(key -> params) building every leaf in one program."""
    std = float(init["std"])
    out_std = std / math.sqrt(2 * m["num_layers"])
    lay = layout(m)

    def build(key):
        flat = {}
        for i, (path, (shape, dt, kind)) in enumerate(sorted(lay.items())):
            if kind == "ones":
                flat[path] = jnp.ones(shape, _DT[dt])
                continue
            s = out_std if path.rsplit("/", 1)[-1] in _OUT_PROJ else std
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * s
            flat[path] = x.astype(_DT[dt])
        return nest(flat)

    return jax.jit(build, out_shardings=out_shardings)

