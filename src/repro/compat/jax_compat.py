"""JAX compatibility layer: the one place that names version-sensitive JAX
API.

Everything in this repo that touches a JAX API whose spelling has moved
between JAX releases goes through this module — the same way the thesis'
system support hides PIM hardware-generation differences from programmers.
New code MUST import these symbols from ``repro.compat`` only (enforced by
tests/test_compat.py and the CI lint in tools/check_jax_compat.py), so the
next move is a one-file change.

Supported: JAX 0.9 with its libtpu (the Pallas TPU namespace is always
present). Older releases are refused at import.

Two behaviours are chosen here rather than inherited from JAX's defaults:

* ``make_mesh`` builds **Auto** axes unless the caller asks otherwise. JAX
  0.9 defaults every axis to Explicit, under which GSPMD-style code —
  ``with_sharding_constraint`` on logical axes, ``dynamic_update_slice`` on
  sharded operands — raises ``ShardingTypeError``; every mesh in this repo
  is built for Auto propagation.
* manual-axis detection (``is_manual_axis`` & co.) combines the abstract
  mesh's axis types with a thread-local record kept by this module's
  ``shard_map`` wrapper, which gives exact answers for nested contexts at
  trace time.
"""
from __future__ import annotations

import contextlib
import functools
import os
import re as _re
import threading
from typing import Any, Callable, Dict, FrozenSet, Optional, Sequence, Tuple

import jax
from jax.experimental import pallas as _pl
from jax.experimental.pallas import tpu as _pltpu
from jax.sharding import AxisType, Mesh

__all__ = [
    "JAX_VERSION",
    "optimization_barrier",
    "axis_size",
    "AxisType",
    "shard_map",
    "make_mesh",
    "abstract_mesh",
    "context_mesh",
    "manual_axis_names",
    "current_axis_types",
    "is_manual_axis",
    "in_manual_context",
    "import_pallas",
    "import_pallas_tpu",
    "pallas_call",
    "pallas_prefetch_grid_spec",
    "pallas_vmem_scratch",
    "tree_map",
    "tree_leaves",
    "tree_flatten",
    "tree_unflatten",
    "tree_structure",
    "tree_reduce",
    "tree_all",
    "describe_support",
    "enable_compilation_cache",
]


def _parse_version(v: str) -> Tuple[int, ...]:
    """Leading numeric release components only ('0.5.0rc1' -> (0, 5, 0))."""
    parts = []
    for p in v.split("."):
        m = _re.match(r"\d+", p)
        if m is None:
            break
        parts.append(int(m.group()))
        if m.group() != p:  # mixed part like '0rc1': stop after its number
            break
    return tuple(parts[:3])


JAX_VERSION: Tuple[int, ...] = _parse_version(jax.__version__)
if JAX_VERSION < (0, 9):
    raise ImportError(f"repro needs jax >= 0.9 (found {jax.__version__})")

optimization_barrier = jax.lax.optimization_barrier
axis_size = jax.lax.axis_size

# ---------------------------------------------------------------------------
# shard_map with trace-time manual-axis recording
# ---------------------------------------------------------------------------
# Thread-local stack of (abstract mesh, frozenset(manual axis names)),
# pushed while the body of a compat shard_map is being traced.
_trace_ctx = threading.local()


def _ctx_stack():
    stack = getattr(_trace_ctx, "stack", None)
    if stack is None:
        stack = _trace_ctx.stack = []
    return stack


@contextlib.contextmanager
def _recording_manual(mesh, manual: FrozenSet[str]):
    stack = _ctx_stack()
    stack.append((abstract_mesh(mesh), manual))
    try:
        yield
    finally:
        stack.pop()


def shard_map(
    f: Callable,
    mesh=None,
    in_specs: Any = None,
    out_specs: Any = None,
    *,
    axis_names: Optional[FrozenSet[str]] = None,
    check_vma: bool = True,
):
    """``jax.shard_map`` that records its manual axes for the queries below.

    ``axis_names`` is the set of mesh axes that are *manual* inside ``f``
    (omitted = all axes manual); the rest stay Auto (partial-manual).
    """
    if mesh is None:
        raise TypeError("shard_map: mesh is required")
    all_axes = frozenset(mesh.axis_names)
    manual = all_axes if axis_names is None else frozenset(axis_names)
    if not manual <= all_axes:
        raise ValueError(
            f"shard_map: manual axes {sorted(manual)} not a subset of mesh "
            f"axes {sorted(all_axes)}")

    @functools.wraps(f)
    def traced(*args, **kwargs):
        with _recording_manual(mesh, manual):
            return f(*args, **kwargs)

    kw: Dict[str, Any] = {"mesh": mesh, "in_specs": in_specs,
                          "out_specs": out_specs, "check_vma": check_vma}
    if manual != all_axes:
        kw["axis_names"] = set(manual)
    return jax.shard_map(traced, **kw)


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------
def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None, axis_types=None) -> Mesh:
    """``jax.make_mesh`` with Auto axes unless ``axis_types`` says otherwise
    (see the module docstring for why Auto is the default here)."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=tuple(axis_types), devices=devices)


def abstract_mesh(mesh):
    """The AbstractMesh view of a (possibly already abstract) mesh."""
    if mesh is None:
        return None
    if isinstance(mesh, Mesh):
        return mesh.abstract_mesh
    return mesh  # already abstract


# ---------------------------------------------------------------------------
# Trace-context queries (manual-axis detection)
# ---------------------------------------------------------------------------
def _native_context() -> Optional[Tuple[Any, FrozenSet[str]]]:
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return None
    manual = frozenset(
        n for n, t in zip(m.axis_names, m.axis_types)
        if t == AxisType.Manual)
    return m, manual


def context_mesh():
    """Innermost shard_map's (abstract) mesh, or None outside any."""
    stack = getattr(_trace_ctx, "stack", None)
    if stack:
        return stack[-1][0]
    native = _native_context()
    return native[0] if native else None


def manual_axis_names() -> FrozenSet[str]:
    """Mesh axes that are Manual in the current tracing context."""
    stack = getattr(_trace_ctx, "stack", None)
    if stack:
        return stack[-1][1]
    native = _native_context()
    return native[1] if native else frozenset()


def current_axis_types() -> Dict[str, "AxisType"]:
    """{axis name: AxisType} for the current context mesh ({} outside)."""
    mesh = context_mesh()
    if mesh is None:
        return {}
    manual = manual_axis_names()
    return {n: (AxisType.Manual if n in manual else AxisType.Auto)
            for n in mesh.axis_names}


def is_manual_axis(name: Optional[str] = None) -> bool:
    """Is ``name`` (or, with None, *any* axis) Manual in the current context?"""
    manual = manual_axis_names()
    return bool(manual) if name is None else name in manual


def in_manual_context() -> bool:
    """True inside a shard_map body with at least one manual axis.

    Model/planner code uses this to skip ``with_sharding_constraint`` —
    under a (partial-)manual map XLA's SPMD partitioner CHECK-fails on many
    constraint/reshard patterns (spmd_partitioner_util.cc), so GSPMD must
    propagate freely there.
    """
    return is_manual_axis(None)


# ---------------------------------------------------------------------------
# Pallas entry points
# ---------------------------------------------------------------------------
def import_pallas():
    """The pallas module (``jax.experimental.pallas``)."""
    return _pl


def import_pallas_tpu():
    """The TPU pallas namespace (``jax.experimental.pallas.tpu``)."""
    return _pltpu


def pallas_call(*args, **kwargs):
    """``pl.pallas_call``."""
    return _pl.pallas_call(*args, **kwargs)


def pallas_prefetch_grid_spec():
    """The scalar-prefetch grid-spec class (``pltpu.PrefetchScalarGridSpec``).

    Scalar-prefetch arguments are available to BlockSpec index maps before
    the kernel body runs — the mechanism that lets the paged decode kernel
    resolve data-dependent page-table lookups into kv block indices.
    """
    return _pltpu.PrefetchScalarGridSpec


def pallas_vmem_scratch(shape: Tuple[int, ...], dtype):
    """A VMEM scratch allocation for ``pallas_call`` scratch shapes."""
    return _pltpu.VMEM(tuple(shape), dtype)


# ---------------------------------------------------------------------------
# Tree utilities
# ---------------------------------------------------------------------------
tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten
tree_structure = jax.tree.structure
tree_reduce = jax.tree.reduce
tree_all = jax.tree.all


def describe_support() -> str:
    """One-line banner naming the JAX this process runs on."""
    return (f"repro.compat: jax {jax.__version__} | default backend "
            f"{jax.default_backend()} | mesh axes Auto by default")


# the checkout root: src/repro/compat/jax_compat.py -> four levels up
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    that directory stays in charge. Otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    directory that moved between runs would never hit. Entry points call
    this (never an import), so tests and library users keep JAX's default.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

