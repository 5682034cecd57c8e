"""Tests for the JAX compatibility layer (repro.compat).

Three families:
  * every exported symbol resolves on the installed JAX (0.9),
  * is_manual_axis / current_axis_types agree with ground truth inside and
    outside shard_map (full- and partial-manual),
  * repo hygiene: version-fragile JAX spellings appear only inside
    src/repro/compat (the rule CI also enforces).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Exports resolve
# ---------------------------------------------------------------------------
def test_all_exports_resolve():
    assert compat.__all__, "compat must declare __all__"
    for name in compat.__all__:
        assert hasattr(compat, name), f"compat.{name} missing"
        assert getattr(compat, name) is not None, f"compat.{name} is None"


def _expected_version(v):
    parts = []
    for p in v.split("."):
        m = re.match(r"\d+", p)
        if m is None:
            break
        parts.append(int(m.group()))
        if m.group() != p:
            break
    return tuple(parts[:3])


def test_version_flags_consistent():
    from repro.compat import jax_compat
    assert compat.JAX_VERSION == _expected_version(jax.__version__)
    # prerelease/dev version strings parse to their release components
    assert jax_compat._parse_version("0.5.0rc1") == (0, 5, 0)
    assert jax_compat._parse_version("0.4.39rc1") == (0, 4, 39)
    assert jax_compat._parse_version("0.7.2.dev123") == (0, 7, 2)
    assert compat.JAX_VERSION >= (0, 9)
    assert "repro.compat" in compat.describe_support()


def test_axis_type_members():
    assert compat.AxisType is jax.sharding.AxisType
    for member in ("Auto", "Explicit", "Manual"):
        assert hasattr(compat.AxisType, member)


def test_make_mesh_roundtrip():
    mesh = compat.make_mesh((1,), ("data",))
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == 1
    # Auto unless asked: jax.make_mesh alone would make every axis Explicit
    assert tuple(mesh.axis_types) == (compat.AxisType.Auto,)
    mesh2 = compat.make_mesh(
        (1,), ("data",), axis_types=(compat.AxisType.Explicit,))
    assert tuple(mesh2.axis_types) == (compat.AxisType.Explicit,)
    mesh3 = compat.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    assert list(mesh3.devices.flat) == jax.devices()[:1]


def test_enable_compilation_cache(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    # a directory named by the environment stays in charge, untouched
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compat.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # otherwise a fixed path inside the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compat.enable_compilation_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compat.enable_compilation_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_tree_utils():
    tree = {"a": jnp.ones((2,)), "b": {"c": jnp.zeros((3,))}}
    leaves = compat.tree_leaves(tree)
    assert len(leaves) == 2
    doubled = compat.tree_map(lambda x: x * 2, tree)
    flat, treedef = compat.tree_flatten(doubled)
    assert compat.tree_unflatten(treedef, flat)["a"][0] == 2.0


def test_optimization_barrier_differentiable():
    # the barrier must be transparent to value_and_grad (the scan bodies of
    # every model family differentiate through it)
    def loss(x):
        y = compat.optimization_barrier(x * 3.0)
        return (y ** 2).sum()

    x = jnp.arange(1.0, 4.0)
    val, grad = jax.value_and_grad(loss)(x)
    assert float(val) == pytest.approx(float((9 * x * x).sum()))
    assert jnp.allclose(grad, 18.0 * x)
    # pytree carries (the scan-body usage) differentiate too
    val2, grads = jax.value_and_grad(
        lambda t: compat.optimization_barrier(t)["a"].sum())({"a": x})
    assert jnp.allclose(grads["a"], 1.0)


def test_pallas_entry_points():
    pl = compat.import_pallas()
    assert hasattr(pl, "pallas_call")
    assert hasattr(compat.import_pallas_tpu(), "VMEM")


# ---------------------------------------------------------------------------
# Manual-axis detection vs ground truth
# ---------------------------------------------------------------------------
def test_manual_detection_outside_shard_map():
    assert not compat.is_manual_axis()
    assert not compat.is_manual_axis("data")
    assert not compat.in_manual_context()
    assert compat.current_axis_types() == {}
    assert compat.manual_axis_names() == frozenset()


def test_manual_detection_full_manual():
    mesh = compat.make_mesh((1,), ("data",))
    seen = {}

    def body(x):
        seen["manual"] = compat.manual_axis_names()
        seen["types"] = compat.current_axis_types()
        seen["is_data"] = compat.is_manual_axis("data")
        seen["in_ctx"] = compat.in_manual_context()
        # ground truth: a Manual axis is usable by name in collectives
        seen["axis_index_ok"] = True
        _ = jax.lax.axis_index("data")
        return x

    out = compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"))(jnp.arange(4.0))
    assert out.shape == (4,)
    assert seen["manual"] == frozenset({"data"})
    assert seen["is_data"] and seen["in_ctx"] and seen["axis_index_ok"]
    assert seen["types"] == {"data": compat.AxisType.Manual}
    # context fully unwound afterwards
    assert not compat.in_manual_context()


def test_manual_detection_partial_manual():
    mesh = compat.make_mesh((1, 1), ("a", "b"))
    seen = {}

    def body(x):
        seen["manual"] = compat.manual_axis_names()
        seen["types"] = compat.current_axis_types()
        return jax.lax.psum(x, "a")

    fn = compat.shard_map(body, mesh=mesh, in_specs=P("a"), out_specs=P(),
                          axis_names=frozenset({"a"}), check_vma=False)
    out = jax.jit(fn)(jnp.arange(4.0))
    assert out.shape == (4,)
    assert seen["manual"] == frozenset({"a"})
    assert seen["types"] == {"a": compat.AxisType.Manual,
                             "b": compat.AxisType.Auto}


def test_context_mesh_nesting():
    mesh = compat.make_mesh((1,), ("data",))
    seen = {}

    def body(x):
        ctx = compat.context_mesh()
        seen["names"] = tuple(ctx.axis_names) if ctx is not None else None
        return x

    compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                     out_specs=P("data"))(jnp.arange(2.0))
    assert seen["names"] == ("data",)
    assert compat.context_mesh() is None


def test_shard_map_flag_spellings_equivalent():
    mesh = compat.make_mesh((1,), ("data",))
    x = jnp.arange(4.0)

    def body(v):
        return v * 2

    for kw in ({"check_vma": False}, {}):
        out = compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data"), **kw)(x)
        assert float(out.sum()) == float(x.sum()) * 2


def test_shard_map_rejects_conflicting_axis_args():
    mesh = compat.make_mesh((1, 1), ("a", "b"))
    with pytest.raises(TypeError):
        compat.shard_map(lambda x: x, in_specs=P(), out_specs=P())
    with pytest.raises(ValueError):
        compat.shard_map(lambda x: x, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names=frozenset({"nope"}))


# ---------------------------------------------------------------------------
# Repo hygiene: fragile spellings only inside the compat package.
# Pattern list lives in tools/check_jax_compat.py (shared with the CI lint
# job) so the two enforcement points cannot drift.
# ---------------------------------------------------------------------------
def _load_checker():
    import importlib.util

    path = os.path.join(REPO, "tools", "check_jax_compat.py")
    spec = importlib.util.spec_from_file_location("check_jax_compat", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_version_fragile_imports_outside_compat():
    checker = _load_checker()
    offenders = checker.find_offenders(REPO)
    assert not offenders, (
        "version-fragile JAX spellings outside repro.compat "
        "(import them from repro.compat instead):\n" + "\n".join(offenders))


def test_pallas_call_sites_import_via_compat():
    checker = _load_checker()
    offenders = checker.find_pallas_offenders(REPO)
    assert not offenders, (
        "pallas call sites must obtain entry points from repro.compat:\n"
        + "\n".join(offenders))


def test_pallas_lint_catches_direct_prefetch_grid_spec(tmp_path):
    # self-test for the paged-KV lint extension: constructing the
    # scalar-prefetch grid spec by its pltpu name must be flagged, while the
    # compat-accessor spelling the paged kernels use passes
    checker = _load_checker()
    pkg = tmp_path / "src" / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "spec = PrefetchScalarGridSpec(num_scalar_prefetch=1)\n")
    offenders = checker.find_pallas_offenders(str(tmp_path))
    assert len(offenders) == 1 and "bad.py" in offenders[0]
    (pkg / "bad.py").write_text(
        "from repro.compat import import_pallas, pallas_prefetch_grid_spec\n"
        "pl = import_pallas()\n"
        "grid_spec = pallas_prefetch_grid_spec()\n"
        "fn = pl.pallas_call(None, grid_spec=grid_spec)\n")
    assert not checker.find_pallas_offenders(str(tmp_path))


def test_fleet_control_plane_stays_jax_free(tmp_path):
    """The fleet dispatcher and ServeFleet facade are pure host bookkeeping:
    no direct jax import is allowed there (version-sensitive symbols could
    only leak in through one), and the repo's own modules must pass."""
    checker = _load_checker()
    assert not checker.find_fleet_offenders(REPO), \
        checker.find_fleet_offenders(REPO)
    # self-test: a jax import in a control-plane module is flagged
    mod = tmp_path / "src" / "repro" / "distributed"
    mod.mkdir(parents=True)
    (mod / "dispatcher.py").write_text(
        "import jax\nfrom repro.distributed import fault_tolerance\n")
    offenders = checker.find_fleet_offenders(str(tmp_path))
    assert len(offenders) == 1 and "dispatcher.py:1" in offenders[0]
    (mod / "dispatcher.py").write_text(
        "from repro.distributed.fault_tolerance import HealthMonitor\n")
    assert not checker.find_fleet_offenders(str(tmp_path))


def test_pallas_prefetch_grid_spec_resolves():
    spec = compat.pallas_prefetch_grid_spec()
    assert spec is compat.import_pallas_tpu().PrefetchScalarGridSpec


def test_pallas_vmem_scratch_resolves():
    scr = compat.pallas_vmem_scratch((8, 128), jnp.float32)
    assert tuple(scr.shape) == (8, 128)
    assert scr.memory_space == compat.import_pallas_tpu().VMEM
