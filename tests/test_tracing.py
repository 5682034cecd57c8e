"""Program scopes and host spans (``repro.launch.tracing``).

Scopes name device work and must change nothing that is compiled; spans
name the engine's host work in a recorded profile, with request ids; the
engine's chunk times stay a bounded record.
"""
import bisect
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, ShapeConfig, get_config
from repro.core.mimdram import plan_sharding, use_plan
from repro.launch import mesh as mesh_lib
from repro.launch import tracing
from repro.launch.engine import (CHUNK_HIST_EDGES, Request, ServeEngine,
                                 chunk_hist, hist_quantile)
from repro.launch.steps import make_train_step
from repro.models import abstract_params, build_model, init_params
from repro.optim import make_optimizer

ARCH = "pimref-100m"
# the scopes of a layer of held experts, which a dense model has not
MOE_SCOPES = ("moe_route", "moe_experts", "moe_combine")


def _held_moe_config():
    """pimref-100m at smoke widths with every layer's FFN two of eight
    experts held, top 4, in a period of a windowed and a full layer."""
    return get_config(ARCH, smoke=True).replace(
        family="moe", num_experts=8, experts_per_token=4, experts_held=2,
        d_ff=32, layer_pattern=("sliding", "full"), sliding_window=8)


def _train_step_hlo(cfg=None) -> str:
    """The guarded train step's optimized HLO at smoke widths."""
    cfg = cfg or get_config(ARCH, smoke=True)
    shape = ShapeConfig("t", 32, 4, "train")
    plan = plan_sharding(cfg, shape, mesh_lib.make_local_mesh(("data",)))
    model = build_model(cfg)
    opt = make_optimizer("adamw", RunConfig())
    step = jax.jit(make_train_step(model, opt, plan, RunConfig(),
                                   guard=True))
    with use_plan(plan):
        params = abstract_params(model.param_specs())
    batch = {k: jax.ShapeDtypeStruct((4, 32), jnp.int32)
             for k in ("tokens", "labels")}
    return step.lower(params, jax.eval_shape(opt.init, params),
                      batch).compile().as_text()


def _strip(hlo: str) -> str:
    """The program without its debug information: the source-location
    tables at the top and each instruction's ``metadata={...}``."""
    lines = hlo.splitlines()
    body = next(i for i, l in enumerate(lines)
                if l.startswith(("%", "ENTRY")))
    return "\n".join([lines[0]] + [re.sub(r", metadata=\{[^}]*\}", "", l)
                                   for l in lines[body:]])


def test_scopes_change_metadata_only(monkeypatch):
    """With every scope a no-op, the train step compiles to the same
    program once metadata is stripped; with them, its ops are named."""
    scoped = _train_step_hlo()
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    plain = _train_step_hlo()
    assert scoped != plain
    assert _strip(scoped) == _strip(plain)
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    for sc in tracing.SCOPES:
        if sc not in MOE_SCOPES:
            assert any(f"/{sc}/" in n or f"({sc})" in n for n in names), sc
    # JAX marks the backward pass's names: what the reader uses
    assert any("transpose(jvp(" in n and "/attn_bwd/" in n for n in names)


def test_held_expert_scopes_change_metadata_only(monkeypatch):
    """A step with held experts names its routing, grouped matmuls and
    combine, inside ``mlp``, in both directions; the names change nothing
    else."""
    scoped = _train_step_hlo(_held_moe_config())
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    assert _strip(scoped) == _strip(_train_step_hlo(_held_moe_config()))
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    for sc in MOE_SCOPES:
        assert any(f"/mlp/{sc}/" in n for n in names), sc
        assert any("transpose(" in n and f"/{sc}/" in n for n in names), sc


def test_kernel_names_are_listed():
    """Every literal ``pallas_call`` name in the kernels is one that
    tracing lists, the flash backward among them."""
    src = os.path.join(os.path.dirname(__file__), "..", "src", "repro",
                       "kernels")
    names = set()
    for path in glob.glob(os.path.join(src, "**", "*.py"), recursive=True):
        with open(path) as f:
            names |= set(re.findall(r'name="(\w+)"', f.read()))
    assert {"flash_bwd", "gmm", "tgmm"} <= names
    assert names <= set(tracing.KERNELS)


def test_names_are_listed():
    """Every scope and span the program opens is one that tracing lists,
    with the ids listed for it."""
    with pytest.raises(AssertionError):
        tracing.scope("not_a_scope")
    src = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    used = {}
    for path in glob.glob(os.path.join(src, "**", "*.py"), recursive=True):
        with open(path) as f:
            text = f.read()
        for name, args in re.findall(r'tracing\.span\("([^"]+)"(.*?)\):\n',
                                     text, re.S):
            used[name] = tuple(re.findall(r"(\w+)=", args))
    assert used == tracing.SPANS
    with tracing.span("engine.chunk", dispatch=0, slots=1):
        pass                                   # no profile: a no-op


def _events(log_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, dict(ev.stats))
                       for ev in line.events
                       if ev.name.startswith(tracing.SPAN_PREFIX))
    return sorted(out, key=lambda e: e[1])


def test_engine_spans_in_a_profile(tmp_path):
    """Three requests through a two-slot engine: each uid is admitted,
    prefilled and retired in that order, and each decode dispatch has its
    chunk and sync spans."""
    try:
        from jax.profiler import ProfileData  # noqa: F401
    except ImportError as e:
        pytest.skip(f"no profile reader in this JAX: {e}")
    cfg = get_config(ARCH, smoke=True)
    plan = plan_sharding(cfg, ShapeConfig("serve", 16, 2, "decode"),
                         mesh_lib.make_local_mesh(("data",)))
    model = build_model(cfg)
    with use_plan(plan):
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, plan, slots=2, prompt_len=8, max_new=6,
                      chunk=3)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=10 + i, tokens=rng.integers(
        1, cfg.vocab_size, 5 + i).astype(np.int32), max_new_tokens=6)
        for i in range(3)]
    with tracing.profile(str(tmp_path)):
        eng.run(reqs)
    evs = _events(str(tmp_path))
    for r in reqs:
        mine = [n for n, _, st in evs if st.get("uid") == r.uid]
        assert mine == ["repro.engine.admit", "repro.engine.prefill",
                        "repro.engine.retire"], (r.uid, mine)
    admit = {st["uid"]: st for n, _, st in evs
             if n == "repro.engine.admit"}
    assert {u: a["prompt_len"] for u, a in admit.items()} == {
        10: 5, 11: 6, 12: 7}
    retire = {st["uid"]: st for n, _, st in evs
              if n == "repro.engine.retire"}
    assert all(st["tokens"] == 6 and st["reason"] == "length"
               for st in retire.values())
    chunks = [st["dispatch"] for n, _, st in evs
              if n == "repro.engine.chunk"]
    syncs = [st["dispatch"] for n, _, st in evs if n == "repro.engine.sync"]
    n = eng.stats["decode_dispatches"]
    assert chunks == syncs == list(range(n)) and n > 0
    assert sum(eng.stats["chunk_hist"]) == n


def test_chunk_histogram_is_bounded_and_reads_quantiles():
    counts = chunk_hist()
    times = np.geomspace(1e-3, 1.0, 1001)          # 1 ms .. 1 s, log-even
    for t in times:
        counts[bisect.bisect_right(CHUNK_HIST_EDGES, t)] += 1
    assert len(counts) == len(CHUNK_HIST_EDGES) + 1 == 82
    assert sum(counts) == len(times)
    for q in (0.5, 0.95):
        true = float(np.quantile(times, q))
        assert abs(hist_quantile(counts, q) / true - 1) < 0.13, q
    assert hist_quantile(chunk_hist(), 0.5) == 0.0
