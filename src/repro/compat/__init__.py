"""JAX compatibility shims — the only sanctioned import path for
version-sensitive JAX symbols (see jax_compat.py)."""
from repro.compat.jax_compat import (  # noqa: F401
    JAX_VERSION,
    AxisType,
    abstract_mesh,
    axis_size,
    context_mesh,
    current_axis_types,
    describe_support,
    enable_compilation_cache,
    import_pallas,
    import_pallas_tpu,
    in_manual_context,
    is_manual_axis,
    make_mesh,
    manual_axis_names,
    optimization_barrier,
    pallas_call,
    pallas_prefetch_grid_spec,
    pallas_vmem_scratch,
    shard_map,
    tree_all,
    tree_flatten,
    tree_leaves,
    tree_map,
    tree_reduce,
    tree_structure,
    tree_unflatten,
)

from repro.compat.jax_compat import __all__  # noqa: F401
