#!/usr/bin/env python3
"""Bring-up smoke of the main paths on one TPU chip (or four).

Drives serving and training through the entry points a user calls, at
published widths, with random weights made from a seed:

* ``serve`` — each Pallas attention kernel the serving path dispatches
  (prefill, decode, paged decode) is first compared with the jnp attention
  path on the same bf16 inputs at the model's widths. Then
  ``repro.launch.serve.make_queue_engine("stablelm-1.6b")`` pinned to one
  chip drains a mixed-length greedy queue twice (cold, then warm; the two
  must be byte-identical), once with the contiguous KV cache and once with
  the paged cache; its compiled prefill and generate programs must hold the
  kernels. A whole-model smoke compares prefill plus one decode step on the
  kernels with the same step on the jnp attention path.
* ``train`` — ``repro.launch.train.train("pimref-100m")``: four guarded
  steps, every loss finite and no step skipped.

With ``--four-chips`` it runs only what exists across chips, each beside
what it is compared with:

* ``dp_train`` — 3 steps of pimref-100m on the 4-way data mesh against the
  same steps on one chip, compared on the first loss, the first gradient
  norm and the weights after the steps; a 4-way run at learning rate 0 must
  fail the same comparison.
* ``fleet`` — a 4-shard in-process ``ServeFleet``, one replica per chip,
  drains the serve queue: exactly one completion per request, byte-identical
  with one engine on chip 0.

Lines before the last are progress and smoke timings: compile and run
seconds per phase, for bring-up only, not metrics. On success the last line
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure, or a JAX that finds no TPU, exits nonzero without that line.

  python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from contextlib import contextmanager
from functools import partial
from typing import Any, Dict, Iterator, List, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import enable_compilation_cache  # noqa: E402
from repro.configs import RunConfig, get_config  # noqa: E402
from repro.kernels.common import on_tpu  # noqa: E402
from repro.launch.engine import Completion, Request  # noqa: E402
from repro.launch.serve import make_fleet, make_queue_engine  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.layers import (PagedKVCache, chunked_attention,  # noqa: E402
                                 paged_from_ring)

SERVE_ARCH = "stablelm-1.6b"
TRAIN_ARCH = "pimref-100m"
PAGE_SIZE = 128
# Each Pallas attention kernel against the jnp attention path run in
# float32 at highest matmul precision on the same bf16 values, by relative
# L2 error of the output. The kernels feed bf16 operands to the MXU with
# float32 accumulation and round the probabilities and the output to bf16:
# about 2^-9 relative each, a few 1e-3 in all. A mis-scaled head, a wrong
# mask position or a wrong page for one slot moves the output by far more.
KERNEL_RTOL = 1e-2
# Secondary whole-model smoke: Pallas vs jnp attention on the logits of a
# 24-layer random-weight bf16 model, where rounding noise compounds (a CPU
# rehearsal put the bf16 jnp path 5.6e-2 from float32 at 2 layers and
# 1.4e-1 at 8). Its bound is that noise, measured in the same run: the
# Pallas path must sit no farther from the jnp path than the jnp path sits
# from the float32 reference, and no more than LOGIT_F32_MARGIN farther
# from that reference than the jnp path is. It catches an order-1 fault
# anywhere on the served path; KERNEL_RTOL is the kernels' own bound.
LOGIT_F32_MARGIN = 1.25
# One chip vs the 4-way data mesh, same batch and initial weights: only the
# order of the loss-mean and gradient reductions differs. The first loss
# (no update yet) must agree to DP_FIRST_LOSS_RTOL and the first gradient
# norm to DP_GRAD_NORM_RTOL (TPU v5e: 2.9e-5 and 9.5e-4, 5.8e-3 for the
# gradient norm on the jnp attention path; 1.2e-2 at smoke widths on the
# CPU). Later losses cannot see the update: on random tokens three steps
# move the loss less than one batch differs from the next. So the updates
# are compared on the weights, in units of the one-chip update |w1 - w0|:
# the 4-way weights must lie within DP_PARAM_GAP of the one-chip weights,
# and the 4-way update must have the same norm to DP_UPDATE_RTOL. AdamW's
# first steps move each weight by about lr * sign(g), so gradient entries
# within rounding noise of zero flip sign, and the chip's bf16 matmul
# passes make that noise large: the
# gap was 0.568 on the Pallas path and 0.534 on the jnp path on a TPU v5e
# (0.04-0.25 on the CPU), the norm ratio within 1.3e-3 of 1. Faults sit
# farther out (CPU, pimref-100m widths cut to one layer): an update from
# one data shard's gradient alone (no all-reduce) gives gap 0.96, ratio
# 0.85 and a gradient norm 160% off; an update from other data, gap 1.26;
# no update, gap 1 and ratio 0 (the learning-rate-0 control runs every
# time); a reversed update, gap 2.
DP_LR = 3e-4
DP_FIRST_LOSS_RTOL = 1e-4
DP_GRAD_NORM_RTOL = 2e-2
DP_PARAM_GAP = 0.75
DP_UPDATE_RTOL = 1e-2
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds of XLA backend compilation, summed from JAX's monitoring
    events (a persistent-cache hit compiles nothing and adds nothing)."""

    def __init__(self) -> None:
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_: Any) -> None:
        if event == _COMPILE_EVENT:
            self.seconds += duration


@contextmanager
def timed(name: str, clock: CompileClock) -> Iterator[None]:
    c0, t0 = clock.seconds, time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    print(f"[{name}] smoke timing (bring-up only, not a metric): "
          f"compile {comp:.1f}s, run {wall - comp:.1f}s", flush=True)


@contextmanager
def env(**knobs: str) -> Iterator[None]:
    """Set trace-time ``REPRO_*`` knobs for the block, then restore."""
    old = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def make_requests(vocab: int, *, n: int, prompt_len: int, gen: int,
                  seed: int) -> List[Request]:
    """Mixed lengths: prompts of prompt_len/8..prompt_len tokens, each
    asking for gen/2..gen new tokens (greedy)."""
    rng = np.random.default_rng(seed)
    return [Request(uid=uid,
                    tokens=rng.integers(1, vocab, int(rng.integers(
                        max(prompt_len // 8, 1), prompt_len + 1))
                    ).astype(np.int32),
                    max_new_tokens=int(rng.integers(max(gen // 2, 1),
                                                    gen + 1)))
            for uid in range(n)]


def check_completions(comps: Sequence[Completion], reqs: Sequence[Request],
                      label: str) -> Dict[int, List[int]]:
    """Exactly one successful completion with tokens per request."""
    got: Dict[int, List[int]] = {}
    for c in comps:
        assert c.uid not in got, f"{label}: uid {c.uid} completed twice"
        assert c.finish_reason != "error", (
            f"{label}: uid {c.uid} failed: {c.error}")
        assert len(c.tokens) > 0, f"{label}: uid {c.uid} has no tokens"
        got[c.uid] = [int(t) for t in c.tokens]
    want = sorted(r.uid for r in reqs)
    assert sorted(got) == want, f"{label}: uids {sorted(got)} != {want}"
    return got


def drain_twice(eng, reqs: Sequence[Request],
                label: str) -> Dict[int, List[int]]:
    """Cold drain (compiles), then the same prompts again under new uids:
    greedy decoding must repeat itself token for token."""
    cold = check_completions(eng.run(list(reqs)), reqs, label)
    shift = 1 + max(r.uid for r in reqs)
    n0 = len(eng.completions)
    eng.run([dataclasses.replace(r, uid=r.uid + shift) for r in reqs])
    warm = {c.uid - shift: [int(t) for t in c.tokens]
            for c in eng.completions[n0:]}
    assert warm == cold, f"{label}: warm drain differs from the cold one"
    return cold


def rel_l2(a: jax.Array, b: jax.Array) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert np.isfinite(a).all() and np.isfinite(b).all(), "non-finite logits"
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def prefill_decode(model, params, tokens: jax.Array, *, impl: str,
                   page_size: int = 0) -> Dict[str, Any]:
    """Every position's prefill logits plus one decode step's, with the
    attention backend and KV layout fixed at trace time; also returns the
    compiled programs' HLO text."""
    S = tokens.shape[1]
    fed = tokens[:, -1:]
    with env(REPRO_ATTN_IMPL=impl, REPRO_KV_PAGES=str(page_size)):
        pre = jax.jit(lambda p, t: model.prefill(
            p, {"tokens": t}, max_len=S + 8, full_logits=True)
        ).lower(params, tokens).compile()
        logits, cache = pre(params, tokens)
        dec = jax.jit(lambda p, c, t: model.decode_step(p, c, t)).lower(
            params, cache, fed).compile()
        step, _ = dec(params, cache, fed)
    return {"prefill": logits, "decode": step,
            "hlo": {"prefill": pre.as_text(), "decode": dec.as_text()}}


def f32_reference(model, params, tokens: jax.Array) -> Dict[str, Any]:
    """The same model computed in float32 at highest matmul precision, on
    the jnp attention path."""
    ref = build_model(model.cfg.replace(compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        return prefill_decode(ref, params, tokens, impl="jnp")


def logit_check(model, params, tokens: jax.Array, page_size: int,
                ref: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Pallas kernels vs the jnp attention path on the same chip, bounded
    by the bf16 noise the f32 reference ``ref`` measures (see
    LOGIT_F32_MARGIN)."""
    pallas = prefill_decode(model, params, tokens, impl="pallas",
                            page_size=page_size)
    jnp_ = prefill_decode(model, params, tokens, impl="jnp",
                          page_size=page_size)
    if on_tpu():
        for what, text in pallas["hlo"].items():
            assert "tpu_custom_call" in text, (
                f"compiled {what} has no Pallas kernel (tpu_custom_call)")
    out = {}
    for what in ("prefill", "decode"):
        err = {"pallas_jnp": rel_l2(pallas[what], jnp_[what]),
               "pallas_f32": rel_l2(pallas[what], ref[what]),
               "jnp_f32": rel_l2(jnp_[what], ref[what])}
        assert (err["pallas_jnp"] <= err["jnp_f32"] and err["pallas_f32"]
                <= LOGIT_F32_MARGIN * err["jnp_f32"]), (
            f"pallas vs jnp {what} logits (page size {page_size}): "
            f"relative L2 {err}")
        out[what] = err
    return out


class Recorder:
    """Passes calls through to a jitted program and keeps the abstract
    arguments of the first call, so that the program can be compiled again
    to read what ran."""

    def __init__(self, fn) -> None:
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            self.args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
                args)
        return self.fn(*args)

    def compiled_text(self) -> str:
        return self.fn.lower(*self.args).compile().as_text()


def _attend(impl: str, q, k, v, q_pos=None, kv_pos=None):
    if q_pos is None:                                   # causal prefill
        return chunked_attention(q, k, v, impl=impl)
    return chunked_attention(q, k, v, q_offset=q_pos, kv_positions=kv_pos,
                             impl=impl)


def kernel_check(*, heads: int, kv_heads: int, head_dim: int,
                 prompt_len: int, cache_len: int, slots: int, page_size: int,
                 seed: int, device: Any) -> Dict[str, float]:
    """Each Pallas attention kernel the serving path dispatches, compiled,
    against the jnp attention path in float32 at highest matmul precision
    on the same bf16 values (see KERNEL_RTOL): causal prefill of two
    prompts; one decode row per slot at mixed depths against a contiguous
    cache; the same against a paged cache whose pages sit at shuffled pool
    rows."""
    rng = np.random.default_rng(seed)

    def bf16(*shape):
        return jax.device_put(jnp.asarray(rng.standard_normal(shape),
                                          jnp.bfloat16), device)

    prompt = [bf16(2, prompt_len, h, head_dim)
              for h in (heads, kv_heads, kv_heads)]
    qd = bf16(slots, 1, heads, head_dim)
    kc, vc = (bf16(slots, cache_len, kv_heads, head_dim) for _ in "kv")
    depth = rng.integers(1, cache_len, slots)
    q_pos = jnp.asarray(depth, jnp.int32)
    ar = np.arange(cache_len)
    kv_pos = jnp.asarray(np.where(ar[None] <= depth[:, None], ar, -1),
                         jnp.int32)
    pk, pv = (paged_from_ring(c, page_size, "off") for c in (kc, vc))
    perm = jnp.asarray(np.concatenate(
        [[0], 1 + rng.permutation(pk.pages.shape[0] - 1)]), jnp.int32)
    pk, pv = (PagedKVCache(jnp.zeros_like(c.pages).at[perm].set(c.pages),
                           perm[c.table]) for c in (pk, pv))
    cases = {"prefill": prompt,
             "decode": [qd, kc, vc, q_pos, kv_pos],
             "paged decode": [qd, pk, pv, q_pos, kv_pos]}
    f32 = partial(jax.tree_util.tree_map, lambda x: x.astype(
        jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating) else x)
    out = {}
    for what, args in cases.items():
        kern = jax.jit(partial(_attend, "pallas")).lower(*args).compile()
        if on_tpu():
            assert "tpu_custom_call" in kern.as_text(), (
                f"compiled {what} attention has no Pallas kernel")
        got = kern(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(partial(_attend, "jnp"))(*f32(args))
        out[what] = rel_l2(got, want)
        assert out[what] <= KERNEL_RTOL, (
            f"{what} kernel vs jnp attention: relative L2 {out[what]} > "
            f"{KERNEL_RTOL}")
    return out


def serve_phase(clock: CompileClock, *, arch: str = SERVE_ARCH,
                smoke: bool = False, slots: int = 8, prompt_len: int = 512,
                gen: int = 64, chunk: int = 16, requests: int = 8,
                page_size: int = PAGE_SIZE, seed: int = 0,
                devices: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    devices = list(devices or jax.devices()[:1])
    cfg = get_config(arch, smoke=smoke)
    vocab = cfg.vocab_size
    out: Dict[str, Any] = {}
    with timed("serve/kernels", clock):
        out["kernels"] = kernel_check(
            heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim or cfg.d_model // cfg.num_heads,
            prompt_len=prompt_len, slots=slots, page_size=page_size,
            cache_len=-(-(prompt_len + gen) // page_size) * page_size,
            seed=seed, device=devices[0])
    print(f"[serve/kernels] relative L2 vs jnp attention in float32 "
          f"{out['kernels']!r} (limit {KERNEL_RTOL})", flush=True)
    reqs = make_requests(vocab, n=requests, prompt_len=prompt_len, gen=gen,
                         seed=seed)
    kw = dict(smoke=smoke, slots=slots, prompt_len=prompt_len, gen=gen,
              chunk=chunk, seed=seed, devices=devices)
    for layout, ps in (("contiguous", 0), ("paged", page_size)):
        with timed(f"serve/{layout}", clock), env(REPRO_KV_PAGES=str(ps)):
            eng = make_queue_engine(arch, **kw)
            assert eng.paged == (ps > 0), f"{layout}: engine layout"
            programs = {"prefill": Recorder(eng._prefill),
                        "generate": Recorder(eng._generate)}
            eng._prefill, eng._generate = (programs["prefill"],
                                           programs["generate"])
            toks = drain_twice(eng, reqs, f"serve/{layout}")
            for what, rec in programs.items():
                text = rec.compiled_text()
                assert "tpu_custom_call" in text or not on_tpu(), (
                    f"{layout}: the engine's compiled {what} program has "
                    "no Pallas kernel")
        out[layout] = sum(len(t) for t in toks.values())
        print(f"[serve/{layout}] {len(toks)} requests, {out[layout]} tokens "
              f"out, each drained twice identically", flush=True)
        model, params = eng.model, eng.params
        del eng                     # its cache; the check needs the memory
        gc.collect()
        if layout == "contiguous":
            rng = np.random.default_rng(seed + 1)
            tokens = jax.device_put(jnp.asarray(
                rng.integers(1, vocab, (2, prompt_len)), jnp.int32),
                devices[0])
            with timed("serve/logits", clock):
                ref = f32_reference(model, params, tokens)
                for check_ps in (0, page_size):
                    err = logit_check(model, params, tokens, check_ps, ref)
                    print(f"[serve/logits] page size {check_ps}: relative "
                          f"L2 {err!r}", flush=True)
                    out[f"logits_ps{check_ps}"] = err
            del ref
        del model, params
        gc.collect()
    return out


def train_phase(clock: CompileClock, *, arch: str = TRAIN_ARCH,
                smoke: bool = False, steps: int = 4, batch: int = 8,
                seq: int = 512,
                devices: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    with timed("train", clock):
        out = train(arch, smoke=smoke, steps=steps, batch=batch, seq=seq,
                    log_every=1, devices=list(devices or jax.devices()[:1]))
    losses = out["losses"]
    assert len(losses) == steps, f"train: {len(losses)} of {steps} steps"
    assert np.isfinite(losses).all(), f"train: non-finite loss {losses}"
    assert out["skipped_steps"] == 0, (
        f"train: the guard skipped {out['skipped_steps']} step(s)")
    print(f"[train] losses {losses!r}", flush=True)
    return {"losses": losses}


def fleet_phase(clock: CompileClock, *, arch: str = SERVE_ARCH,
                smoke: bool = False, shards: int = 4, slots: int = 8,
                prompt_len: int = 512, gen: int = 64, chunk: int = 16,
                requests: int = 8, seed: int = 0) -> Dict[str, Any]:
    vocab = get_config(arch, smoke=smoke).vocab_size
    reqs = make_requests(vocab, n=requests, prompt_len=prompt_len, gen=gen,
                         seed=seed)
    kw = dict(smoke=smoke, slots=slots, prompt_len=prompt_len, gen=gen,
              chunk=chunk, seed=seed)
    with timed("fleet", clock):
        fleet = make_fleet(arch, shards=shards, backend="inproc", **kw)
        try:
            devs = [sh.eng.plan.mesh.devices.flat[0] for sh in fleet.shards]
            assert len(set(devs)) == min(shards, jax.device_count()), (
                f"fleet shards share devices: {devs}")
            fleet.run(reqs)
            got = check_completions(fleet.completions, reqs, "fleet")
        finally:
            fleet.close()
        del fleet
        gc.collect()
    with timed("fleet/reference", clock):
        ref = make_queue_engine(arch, devices=jax.devices()[:1], **kw)
        want = check_completions(ref.run(list(reqs)), reqs,
                                 "fleet/reference")
        del ref
        gc.collect()
    for uid, toks in want.items():
        assert got[uid] == toks, f"fleet: uid {uid} differs from one engine"
    print(f"[fleet] {shards} shards on {len(set(devs))} devices: "
          f"{len(got)} completions byte-identical with one engine",
          flush=True)
    return {"shards": shards, "devices": len(set(devs))}


def host_leaves(tree: Any) -> List[np.ndarray]:
    return [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(tree)]


def tree_dist(a: Sequence[np.ndarray], b: Sequence[np.ndarray]) -> float:
    return float(np.sqrt(sum(np.sum(np.square(
        x.astype(np.float64) - y.astype(np.float64))) for x, y in zip(a, b))))


def dp_agreement(ref: Dict[str, Any], got: Dict[str, Any],
                 w0: Sequence[np.ndarray]) -> Dict[str, float]:
    """How far run ``got`` lies from run ``ref``, both started from the
    weights ``w0`` on the same batches (see DP_PARAM_GAP)."""
    step = tree_dist(ref["params"], w0)
    return {"first_loss": abs(got["losses"][0] - ref["losses"][0])
            / abs(ref["losses"][0]),
            "grad_norm": abs(got["grad_norms"][0] - ref["grad_norms"][0])
            / ref["grad_norms"][0],
            "param_gap": tree_dist(got["params"], ref["params"]) / step,
            "update_ratio": tree_dist(got["params"], w0) / step}


def check_dp(m: Dict[str, float], label: str) -> None:
    assert (m["first_loss"] <= DP_FIRST_LOSS_RTOL
            and m["grad_norm"] <= DP_GRAD_NORM_RTOL
            and m["param_gap"] <= DP_PARAM_GAP
            and abs(m["update_ratio"] - 1) <= DP_UPDATE_RTOL), (
        f"{label} drifts from one chip: {m}")


def dp_train_phase(clock: CompileClock, *, arch: str = TRAIN_ARCH,
                   smoke: bool = False, steps: int = 3, batch: int = 8,
                   seq: int = 512, n: int = 4) -> Dict[str, Any]:
    def run(ways: int, lr: float, label: str, n_steps: int = steps):
        cfg = RunConfig(total_steps=steps, learning_rate=lr, warmup_steps=0,
                        microbatches=1)
        with timed(f"dp_train/{label}", clock):
            out = train(arch, smoke=smoke, steps=n_steps, batch=batch,
                        seq=seq, run=cfg, log_every=1,
                        devices=jax.devices()[:ways])
        assert out["skipped_steps"] == 0, f"{label}: skipped steps"
        assert len(out["losses"]) == n_steps and np.isfinite(
            out["losses"]).all(), f"{label}: losses {out['losses']}"
        return {"losses": out["losses"], "grad_norms": out["grad_norms"],
                "params": host_leaves(out["params"])}

    w0 = run(1, DP_LR, "init", n_steps=0)["params"]
    ref = run(1, DP_LR, "1-way")
    got = run(n, DP_LR, f"{n}-way")
    ctrl = run(n, 0.0, f"{n}-way lr=0 control")
    m, mc = dp_agreement(ref, got, w0), dp_agreement(ref, ctrl, w0)
    for label, r in (("1-way", ref), (f"{n}-way", got), ("control", ctrl)):
        print(f"[dp_train] {label}: losses {r['losses']!r} grad norms "
              f"{r['grad_norms']!r}", flush=True)
    print(f"[dp_train] {n}-way vs 1-way {m!r}; lr=0 control {mc!r} (limits "
          f"first loss {DP_FIRST_LOSS_RTOL}, grad norm {DP_GRAD_NORM_RTOL}, "
          f"param gap {DP_PARAM_GAP}, update ratio 1 +- {DP_UPDATE_RTOL})",
          flush=True)
    check_dp(m, f"{n}-way")
    try:
        check_dp(mc, "lr=0 control")
    except AssertionError:
        pass
    else:
        raise AssertionError(f"the lr=0 control passed the check: {mc}")
    return {"agreement": m, "control": mc}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet and data-parallel phases, on "
                    "four chips, each against one chip")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {enable_compilation_cache()}", flush=True)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    if args.four_chips:
        dp_train_phase(clock)
        fleet_phase(clock)
    else:
        serve_phase(clock)
        train_phase(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
