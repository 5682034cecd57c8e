"""Names for the profiler: program scopes for device work, spans for host work.

Device work inside jitted programs is named with :func:`scope`, a
``jax.named_scope``: the name lands in each operation's ``op_name`` metadata
(``jit(train_step)/.../attn/dot_general``) and so in a profile's trace, and
changes nothing that is compiled. JAX wraps the names of backward operations
in ``transpose(jvp(...))``, which is how a trace reader tells a scope's
forward work from its backward work. Host work is named with :func:`span`, a
``jax.profiler.TraceAnnotation`` under the ``repro.`` prefix whose keyword
arguments (request ids, lengths) become the event's stats. Outside a
recorded profile a span costs the annotation's own enabled check.

Every name a trace reader or PERF.md uses is listed here.

    PYTHONPATH=src python -m repro.launch.train --arch pimref-100m \
        --steps 8 --profile-dir /tmp/profile     # then open it in xprof
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Iterator, Optional

import jax

# device scopes, by what they hold: ``layers`` is the scanned block stack's
# own work (slicing each layer's weights out of the stacked parameters,
# writing residuals and gradients into stacked buffers); ``attn``, ``mlp``
# and ``attn_bwd`` (the flash backward: the Pallas kernel with its layout
# work, or the jnp recompute backward) lie inside it. A layer of held experts
# splits its ``mlp`` into ``moe_route`` (router, top-k, the sort into the
# expert buffer), ``moe_experts`` (the grouped matmuls) and ``moe_combine``
# (the gather back to tokens, weighted by the gates)
SCOPES = ("embed", "layers", "attn", "attn_bwd", "mlp", "moe_route",
          "moe_experts", "moe_combine", "head_loss", "optimizer", "guard")
# Pallas kernels by their ``pallas_call`` name, which names the kernel's op
# in a profile (``pallas:flash_bwd``): ``flash_fwd`` and ``flash_bwd`` are
# the training attention's forward and backward; ``gmm`` and ``tgmm`` the
# held experts' grouped matmul (forward and input gradient) and its weight
# gradient
KERNELS = ("flash_fwd", "flash_bwd", "flash_decode", "flash_decode_paged",
           "flash_decode_quant", "flash_decode_paged_quant", "quant_matmul",
           "rglru_scan", "narrow_required_bits", "narrow_pack_int4",
           "narrow_unpack_int4", "gmm", "tgmm")
SPAN_PREFIX = "repro."
# host spans (without the prefix) and the ids each carries
SPANS = {
    "engine.admit": ("uid", "prompt_len"),
    "engine.prefill": ("uid", "bucket"),
    "engine.chunk": ("dispatch", "slots"),
    "engine.sync": ("dispatch",),
    "engine.retire": ("uid", "tokens", "reason"),
    "train.step": ("step",),
}


def scope(name: str):
    """A ``jax.named_scope`` for work inside a jitted program."""
    assert name in SCOPES, name
    return jax.named_scope(name)


def span(name: str, **ids):
    """A host span ``repro.<name>`` (one of :data:`SPANS`) carrying ``ids``
    as its stats."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **ids)


@contextmanager
def profile(log_dir: Optional[str]) -> Iterator[None]:
    """Record a profile of the block into ``log_dir`` (nothing when None)."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
