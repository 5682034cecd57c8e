"""Serving driver: pre-sized cache prefill + fused on-device decode.

Two decode engines share one pre-sized cache layout (``model.init_cache``
sized to prompt_len + gen at prefill; no repad between phases):

  * ``loop``  — the per-token baseline: one jit dispatch + one host sync per
    generated token (what dispatch-bound PIM serving looks like).
  * ``fused`` — ``make_generate_step``: the whole decode loop runs inside one
    jit via ``jax.lax.scan`` (on-device sampling, cache donated/updated in
    place): 1 dispatch + 1 host sync per ``chunk`` tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch pimref-100m \
      --batch 4 --prompt-len 32 --gen 16 [--engine fused|loop] [--mode queue]
  PYTHONPATH=src python -m repro.launch.serve --arch pimref-100m \
      --mode queue --requests 4 --profile-dir /tmp/profile   # engine spans
"""
from __future__ import annotations

import argparse
import os
import signal
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import enable_compilation_cache
from repro.configs import ALL_IDS, ShapeConfig, get_config
from repro.core.mimdram import plan_sharding, use_plan
from repro.distributed.chaos import ChaosConfig, ShardChaosConfig
from repro.distributed.fault_tolerance import (PreemptionHandler,
                                               RestartManifest)
from repro.kernels.common import on_tpu
from repro.launch import mesh as mesh_lib
from repro.launch import tracing
from repro.launch.engine import Request, ServeEngine
from repro.launch.fleet import ServeFleet
from repro.launch.steps import (make_decode_step, make_serving_jits, named,
                                sample_tokens, spec_config)
from repro.models import build_model, init_params
from repro.models.module import param_pspecs

# env knobs captured into (and replayed from) a serving RestartManifest so a
# restarted process traces the same cache layout / kernels / drafter
_SERVE_ENV_KNOBS = ("REPRO_KV_PAGES", "REPRO_KV_QUANT", "REPRO_SPEC_DECODE",
                    "REPRO_SPEC_K", "REPRO_ATTN_IMPL")


def serving_config(arch: str, smoke: bool):
    """``arch``'s config with bf16 weights, as deployments serve them (the
    planner sizes serving cells the same way). Every weight the model casts
    to the compute dtype at use comes out bit-identical; norms and other
    explicitly-f32 parameters stay f32."""
    return get_config(arch, smoke=smoke).replace(param_dtype="bfloat16")


def _clone(tree):
    """Deep-copy a pytree, preserving each leaf's sharding (so a warmup call
    on the clone has the same jit signature as the real call)."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(jnp.array(x), x.sharding), tree)


def serve(arch: str, *, smoke: bool = True, batch: int = 4,
          prompt_len: int = 32, gen: int = 16, seed: int = 0,
          engine: str = "fused", chunk: int = 8, temperature: float = 0.0,
          top_k: int = 0, warmup: bool = True, spec: Optional[str] = None,
          spec_k: Optional[int] = None) -> Dict[str, Any]:
    """Prefill a synthetic batch then decode ``gen`` tokens per sequence.

    Returns tokens plus timing/dispatch metrics; with ``temperature == 0``
    both engines produce byte-identical greedy tokens — including with
    speculative decoding (``spec``/``spec_k``; default: the
    REPRO_SPEC_DECODE / REPRO_SPEC_K knobs), which additionally reports
    ``accepted_len_per_draft``.
    """
    assert engine in ("fused", "loop"), engine
    cfg = serving_config(arch, smoke)
    max_len = prompt_len + gen
    shape = ShapeConfig("serve", seq_len=max_len, global_batch=batch,
                        mode="decode")
    mesh = mesh_lib.make_local_mesh(("data",))
    plan = plan_sharding(cfg, shape, mesh)
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    with use_plan(plan):
        params = init_params(model.param_specs(), key)

    spec, spec_k = spec_config(model, spec, spec_k)
    if engine == "loop":
        spec = "off"                 # per-token baseline never speculates
    prefill, generate, rep, cache_sh = make_serving_jits(
        model, plan, max_len=max_len, chunk=chunk, temperature=temperature,
        top_k=top_k, spec=spec, spec_k=spec_k)
    decode = jax.jit(make_decode_step(model, plan), donate_argnums=(1,),
                     out_shardings=(None, cache_sh))
    n_chunks = -(-gen // chunk)

    rng = np.random.default_rng(seed)
    pre_batch: Dict[str, Any] = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)}
    if cfg.family == "vlm":
        npatch = min(cfg.num_patches, prompt_len // 2)
        pre_batch["tokens"] = pre_batch["tokens"][:, : prompt_len - npatch]
        pre_batch["patch_embeds"] = jnp.asarray(
            rng.standard_normal((batch, npatch, cfg.d_model)), jnp.float32)
    if cfg.family == "audio":
        pre_batch["src_embeds"] = jnp.asarray(
            rng.standard_normal((batch, prompt_len, cfg.d_model)), jnp.float32)

    t0 = time.time()
    logits, cache = prefill(params, pre_batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tok = jax.device_put(
        jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), rep)
    gkey = jax.device_put(jax.random.PRNGKey(seed + 1), rep)

    if spec != "off":            # drafter history, seeded with the prompts
        tokf = np.asarray(pre_batch["tokens"])
        hcap = tokf.shape[1] + gen + chunk * (spec_k + 1)
        h0 = np.zeros((batch, hcap), np.int32)
        h0[:, :tokf.shape[1]] = tokf
        hist = jax.device_put(jnp.asarray(h0), rep)
        hist_len = jax.device_put(
            jnp.full((batch,), tokf.shape[1], jnp.int32), rep)

    eos = jnp.int32(-1)          # batch mode: length-only stopping
    if warmup:     # compile outside the timed region (clone: both jits donate)
        if engine == "loop":
            jax.block_until_ready(decode(params, _clone(cache), tok))
        elif spec != "off":
            jax.block_until_ready(
                generate(params, _clone(cache), tok, gkey, eos, _clone(hist),
                         _clone(hist_len))[5])
        else:
            jax.block_until_ready(
                generate(params, _clone(cache), tok, gkey, eos)[5])

    step_times: List[float] = []
    out_tokens: List[np.ndarray] = []
    dispatches = 0
    t0 = time.time()
    if engine == "loop":
        for _ in range(gen):
            ts = time.perf_counter()
            out_tokens.append(np.asarray(tok[:, 0]))    # host sync, every token
            logits, cache = decode(params, cache, tok)
            if temperature > 0:
                gkey, sub = jax.random.split(gkey)
                nxt = sample_tokens(logits[:, -1], sub, temperature, top_k)
            else:
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            tok = jax.device_put(nxt[:, None], rep)
            dispatches += 1
            step_times.append(time.perf_counter() - ts)
        jax.block_until_ready(tok)
        toks = np.stack(out_tokens, axis=1)
        per_tok = np.asarray(step_times)
    elif spec != "off":
        # draft-verify chunks commit a variable 1..spec_k+1 tokens per
        # iteration; drain the compacted buffers until every row has `gen`
        rows: List[List[int]] = [[] for _ in range(batch)]
        acc_sum = acc_iters = 0
        while min(len(r) for r in rows) < gen:
            ts = time.perf_counter()
            (cache, tok, gkey, _done, n_valid, toks_d, hist, hist_len, acc,
             _failed) = generate(params, cache, tok, gkey, eos, hist, hist_len)
            tb = np.asarray(toks_d)                     # host sync, per chunk
            nv = np.asarray(n_valid)
            live = np.asarray(acc)[np.asarray(acc) >= 0]
            acc_iters += int(live.size)
            acc_sum += int(live.sum())
            for r in range(batch):
                rows[r].extend(tb[r, : nv[r]].tolist())
            dispatches += 1
            step_times.append(time.perf_counter() - ts)
        toks = np.asarray([r[:gen] for r in rows], np.int32)
        per_tok = np.full(gen, sum(step_times) / gen)
    else:
        chunks: List[np.ndarray] = []
        for _ in range(n_chunks):
            ts = time.perf_counter()
            cache, tok, gkey, _done, _n, toks_d, _failed = generate(
                params, cache, tok, gkey, eos)
            chunks.append(np.asarray(toks_d))           # host sync, per chunk
            dispatches += 1
            step_times.append(time.perf_counter() - ts)
        toks = np.concatenate(chunks, axis=1)[:, :gen]
        per_tok = np.repeat(np.asarray(step_times) / chunk, chunk)[:gen]
    t_decode = time.time() - t0

    out = {
        "tokens": toks,
        "prefill_s": t_prefill,
        "decode_s_per_tok": t_decode / max(gen, 1),
        "throughput_tok_s": batch * gen / max(t_decode, 1e-9),
        "dispatches": dispatches,
        "dispatches_per_token": dispatches / max(gen, 1),
        "per_token_p50_s": float(np.percentile(per_tok, 50)),
        "per_token_p95_s": float(np.percentile(per_tok, 95)),
    }
    if spec != "off":
        out["accepted_len_per_draft"] = acc_sum / max(acc_iters, 1)
    return out


def make_queue_engine(arch: str, *, smoke: bool = True, slots: int = 4,
                      prompt_len: int = 32, gen: int = 16, chunk: int = 8,
                      seed: int = 0, temperature: float = 0.0, top_k: int = 0,
                      spec: Optional[str] = None, spec_k: Optional[int] = None,
                      devices: Optional[Sequence[Any]] = None,
                      **engine_kwargs: Any) -> ServeEngine:
    """Build a fresh :class:`ServeEngine` for ``arch`` (shared by queue mode,
    the chaos smokes, and checkpoint/restore) on ``devices`` (default: every
    local device; ``jax.devices()[i:i + 1]`` pins it to one chip), with its
    params made on and placed over exactly those devices. ``engine_kwargs``
    forwards the robustness knobs (``max_queue``, ``deadline_ms``,
    ``chaos``, ``page_pool_pages``, ...)."""
    cfg = serving_config(arch, smoke)
    mesh = mesh_lib.make_local_mesh(("data",), devices)
    plan = plan_sharding(
        cfg, ShapeConfig("serve", prompt_len + gen, slots, "decode"), mesh)
    model = build_model(cfg)
    specs = model.param_specs()
    with use_plan(plan), jax.default_device(mesh.devices.flat[0]):
        params = init_params(specs, jax.random.PRNGKey(seed))
    params = jax.device_put(params, named(plan, param_pspecs(specs, plan)))
    return ServeEngine(model, params, plan, slots=slots, prompt_len=prompt_len,
                       max_new=gen, chunk=chunk, temperature=temperature,
                       top_k=top_k, seed=seed, spec=spec, spec_k=spec_k,
                       **engine_kwargs)


def synth_requests(arch: str, *, smoke: bool = True, requests: int = 10,
                   prompt_len: int = 32, gen: int = 16, seed: int = 0,
                   shared_prefix: int = 0,
                   repeat_period: int = 0) -> List[Request]:
    """The synthetic mixed-length request stream used by queue mode — kept
    separate from the engine so a restore-verify run can rebuild the exact
    same queue the preempted process was draining."""
    cfg = get_config(arch, smoke=smoke)
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, shared_prefix).astype(np.int32)
    reqs = []
    for i in range(requests):
        n = int(rng.integers(max(4, shared_prefix + 1), prompt_len + 1))
        if repeat_period > 0:
            period = rng.integers(1, cfg.vocab_size,
                                  repeat_period).astype(np.int32)
            toks = np.tile(period, -(-n // repeat_period))[:n]
        else:
            toks = rng.integers(1, cfg.vocab_size, n).astype(np.int32)
        toks[:shared_prefix] = prefix
        reqs.append(Request(
            uid=i, tokens=toks,
            max_new_tokens=int(rng.integers(max(gen // 2, 1), gen + 1))))
    return reqs


def serve_queue(arch: str, *, smoke: bool = True, slots: int = 4,
                requests: int = 10, prompt_len: int = 32, gen: int = 16,
                chunk: int = 8, seed: int = 0, temperature: float = 0.0,
                top_k: int = 0, shared_prefix: int = 0,
                repeat_period: int = 0, spec: Optional[str] = None,
                spec_k: Optional[int] = None,
                max_queue: Optional[int] = None,
                deadline_ms: Optional[float] = None,
                chaos: Optional[ChaosConfig] = None,
                page_pool_pages: Optional[int] = None,
                stop: Optional[Callable[[ServeEngine], bool]] = None,
                ) -> ServeEngine:
    """Continuous batching: drain a queue of mixed-length synthetic requests
    through a :class:`ServeEngine`; returns the drained engine (stats +
    completions). ``shared_prefix > 0`` gives every request the same first
    tokens (a common system prompt) — with the paged cache, concurrent slots
    then hash-cons their full prefix pages instead of duplicating them.
    ``repeat_period > 0`` tiles each prompt from a short per-request period
    (the lookup-friendly repetitive-suffix workload for the n-gram drafter);
    ``spec``/``spec_k`` select the speculative-decoding drafter (default:
    the env knobs). Robustness knobs: ``max_queue`` bounds the admission
    queue, ``deadline_ms`` retires overdue requests, ``chaos`` arms seeded
    fault injection, ``page_pool_pages`` shrinks the paged-cache pool, and
    ``stop(engine)`` halts the drain early (preemption)."""
    eng = make_queue_engine(
        arch, smoke=smoke, slots=slots, prompt_len=prompt_len, gen=gen,
        chunk=chunk, seed=seed, temperature=temperature, top_k=top_k,
        spec=spec, spec_k=spec_k, max_queue=max_queue, deadline_ms=deadline_ms,
        chaos=chaos, page_pool_pages=page_pool_pages)
    reqs = synth_requests(arch, smoke=smoke, requests=requests,
                          prompt_len=prompt_len, gen=gen, seed=seed,
                          shared_prefix=shared_prefix,
                          repeat_period=repeat_period)
    eng.run(reqs, stop=(lambda: stop(eng)) if stop is not None else None)
    return eng


def make_fleet(arch: str, *, shards: int = 2, backend: str = "inproc",
               smoke: bool = True, slots: int = 4, prompt_len: int = 32,
               gen: int = 16, chunk: int = 8, seed: int = 0,
               temperature: float = 0.0, top_k: int = 0,
               spec: Optional[str] = None, spec_k: Optional[int] = None,
               fleet_chaos: Optional[ShardChaosConfig] = None,
               checkpoint_every: int = 1, manifest_dir: Optional[str] = None,
               miss_suspect: int = 2, miss_dead: int = 4,
               heartbeat_timeout_s: float = 120.0, max_replays: int = 2,
               **engine_kwargs: Any) -> ServeFleet:
    """Build a :class:`ServeFleet` of identical engine shards.

    Every shard gets the same arch/seed/knobs, so any shard decodes any
    request byte-identically — the property failover replay rests on.
    ``inproc`` shards are one-device replicas: shard ``i`` lives on local
    device ``i mod n``. The ``mp`` backend additionally records the serving
    env knobs so spawned workers trace the same cache layout / kernels /
    drafter; it is the CPU stand-in for a multi-host fleet, and refuses a
    TPU host, where every worker would contend for the same chips."""
    if backend == "mp" and on_tpu():
        raise ValueError(
            "fleet backend 'mp' cannot run on a TPU host: a chip belongs to "
            "one process, so spawned workers would contend for it; use "
            "backend='inproc', which places one replica on each chip")
    ekw = dict(arch=arch, smoke=smoke, slots=slots, prompt_len=prompt_len,
               gen=gen, chunk=chunk, seed=seed, temperature=temperature,
               top_k=top_k, spec=spec, spec_k=spec_k, **engine_kwargs)
    factory = worker_spec = None
    if backend == "mp":
        worker_spec = {"engine": ekw,
                       "env": {k: os.environ[k] for k in _SERVE_ENV_KNOBS
                               if k in os.environ}}
    else:
        devs = jax.devices()
        factory = lambda sid: make_queue_engine(  # noqa: E731
            devices=[devs[sid % len(devs)]], **ekw)
    return ServeFleet(factory, shards=shards, backend=backend,
                      worker_spec=worker_spec, chaos=fleet_chaos,
                      checkpoint_every=checkpoint_every,
                      manifest_dir=manifest_dir, miss_suspect=miss_suspect,
                      miss_dead=miss_dead,
                      heartbeat_timeout_s=heartbeat_timeout_s,
                      max_replays=max_replays, seed=seed)


def serve_fleet(arch: str, *, smoke: bool = True, shards: int = 2,
                backend: str = "inproc", slots: int = 4, requests: int = 10,
                prompt_len: int = 32, gen: int = 16, chunk: int = 8,
                seed: int = 0, temperature: float = 0.0, top_k: int = 0,
                shared_prefix: int = 0, repeat_period: int = 0,
                spec: Optional[str] = None, spec_k: Optional[int] = None,
                **fleet_kwargs: Any) -> ServeFleet:
    """Drain the queue-mode synthetic request stream through a sharded
    fleet; returns the drained fleet (caller closes it). The request stream
    is identical to :func:`serve_queue`'s, so a 1-shard reference engine
    drains the exact same queue for byte-identity verification."""
    fleet = make_fleet(arch, shards=shards, backend=backend, smoke=smoke,
                       slots=slots, prompt_len=prompt_len, gen=gen,
                       chunk=chunk, seed=seed, temperature=temperature,
                       top_k=top_k, spec=spec, spec_k=spec_k, **fleet_kwargs)
    reqs = synth_requests(arch, smoke=smoke, requests=requests,
                          prompt_len=prompt_len, gen=gen, seed=seed,
                          shared_prefix=shared_prefix,
                          repeat_period=repeat_period)
    fleet.run(reqs)
    return fleet


def _print_fleet_stats(fleet: ServeFleet) -> None:
    s = fleet.stats
    print(f"fleet: {fleet.n_shards} shards ({fleet.backend}), "
          f"{len(fleet.completions)} requests, {s['tokens_out']} tokens in "
          f"{s['wall_seconds']:.2f}s ({s['tokens_per_second']:.1f} tok/s), "
          f"{s['fleet_steps']} fleet steps, {s['checkpoints']} checkpoints")
    if (s["failovers"] or s["heartbeat_misses"] or s["error_completions"]
            or fleet.chaos_events):
        print(f"fleet robust: {s['failovers']} failovers "
              f"({s['replays']} replays, {s['shard_lost']} shard_lost), "
              f"{s['heartbeat_misses']} heartbeat misses "
              f"({s.get('suspects', 0)} suspects, "
              f"{s.get('recoveries', 0)} recoveries, "
              f"{s.get('deaths', 0)} deaths), "
              f"{s['error_completions']} error completions, "
              f"{len(fleet.chaos_events)} chaos events")
    for row in fleet.per_shard_stats():
        print(f"  shard {row['shard']} [{row['state']}]: "
              f"{row['tokens_out']} tokens, {row['dispatches']} dispatches, "
              f"{row['tok_s']:.1f} tok/s, p50 {row['p50_ms']:.1f}ms, "
              f"p95 {row['p95_ms']:.1f}ms")


def save_serve_manifest(path: str, eng: ServeEngine, *, arch: str,
                        smoke: bool, slots: int, prompt_len: int, gen: int,
                        chunk: int,
                        queue: Optional[Dict[str, Any]] = None) -> None:
    """Write a serving :class:`RestartManifest`: the engine snapshot plus the
    engine/env config a restarted process needs to rebuild identical jits."""
    snap = eng.snapshot()
    snap["engine"] = {
        "arch": arch, "smoke": smoke, "slots": slots,
        "prompt_len": prompt_len, "gen": gen, "chunk": chunk,
        "top_k": eng._top_k, "spec": eng.spec, "spec_k": eng.spec_k,
        "env": {k: os.environ[k] for k in _SERVE_ENV_KNOBS
                if k in os.environ},
    }
    if queue is not None:
        snap["engine"]["queue"] = queue
    RestartManifest(
        step=eng.stats["decode_dispatches"], checkpoint_dir="",
        mesh_shape=[jax.device_count()], mesh_axes=["data"],
        data_seed=eng.seed, arch=arch, shape="serve",
        straggler_events=list(eng._straggler.flagged), serve=snap,
    ).save(path)


def restore_serve(path: str) -> ServeEngine:
    """Rebuild a :class:`ServeEngine` from a serving manifest and drain it.

    Env knobs recorded at snapshot time are replayed before tracing so the
    restored process uses the same cache layout / kernels / drafter. With the
    paged cache, in-flight requests resume from ``prompt + produced`` (page
    positions are bucket-independent); the contiguous layout regenerates from
    the original prompt. Both drain to byte-identical greedy completions.
    """
    man = RestartManifest.load(path)
    assert man.serve is not None, f"{path}: not a serving manifest"
    snap = man.serve
    ecfg = snap["engine"]
    for k, v in ecfg.get("env", {}).items():
        os.environ[k] = v
    prompt_len = ecfg["prompt_len"]
    if os.environ.get("REPRO_KV_PAGES", "0") not in ("", "0"):
        # paged resume re-prefills prompt + produced; the prompt bucket must
        # fit the longest such prefix (positions are true, so growing the
        # bucket cannot change surviving tokens)
        need = max((len(d["tokens"]) + len(d.get("produced", []))
                    for d in snap.get("queued", []) + snap.get("active", [])),
                   default=0)
        prompt_len = max(prompt_len, need)
    eng = make_queue_engine(
        ecfg["arch"], smoke=ecfg["smoke"], slots=ecfg["slots"],
        prompt_len=prompt_len, gen=ecfg["gen"], chunk=ecfg["chunk"],
        seed=snap["seed"], temperature=snap["temperature"],
        top_k=ecfg.get("top_k", 0), spec=ecfg.get("spec"),
        spec_k=ecfg.get("spec_k"))
    eng.load_snapshot(snap)
    eng.run()
    return eng


def _print_queue_stats(eng: ServeEngine) -> None:
    s = eng.stats
    print(f"{len(eng.completions)} requests, {s['tokens_out']} tokens in "
          f"{s['wall_seconds']:.2f}s ({s['tokens_per_second']:.1f} tok/s, "
          f"{s['dispatches_per_token']:.3f} dispatches/token, "
          f"{s['prefills']} prefills)")
    print(f"kv: {s['kv_hbm_bytes_peak'] / 1e6:.2f} MB peak "
          f"({s['kv_bytes_per_token']:.0f} B/token"
          + (f", {s['kv_pages_peak']} pages peak, "
             f"{s['prefix_hits']} prefix hits" if eng.paged else "")
          + ")")
    if eng.spec != "off":
        print(f"spec: mode={eng.spec} k={eng.spec_k} accepted_len/draft="
              f"{s['spec_accepted_len_per_draft']:.3f} "
              f"accept hist={s['spec_accept_hist']}")
    robust = (s["error_completions"] or s["deadline_miss"] or s["retries"]
              or s["shed_events"] or s["admission_blocked"]
              or eng.chaos_events)
    if robust:
        print(f"robust: {s['error_completions']} error completions "
              f"({s['deadline_miss']} deadline misses), "
              f"{s['retries']} retries, {s['shed_events']} shed events, "
              f"{s['straggler_events']} stragglers, "
              f"{s['admission_blocked']} admission stalls, "
              f"queue peak {s['queue_peak']}, "
              f"{len(eng.chaos_events)} chaos events")


def _assert_identical(eng: ServeEngine, ref: ServeEngine, label: str,
                      skip_uids=()) -> int:
    """Assert ``eng``'s non-error completions match ``ref`` byte-for-byte
    (minus ``skip_uids``); returns how many were compared."""
    got = {c.uid: c for c in eng.completions}
    want = sorted(c.uid for c in ref.completions)
    assert sorted(got) == want, (
        f"{label}: completion uids {sorted(got)} != {want}")
    checked = 0
    for c in ref.completions:
        g = got[c.uid]
        if g.finish_reason == "error" or c.uid in skip_uids:
            continue
        assert list(np.asarray(g.tokens)) == list(np.asarray(c.tokens)), (
            f"{label} mismatch on uid={c.uid}: "
            f"{np.asarray(g.tokens)} != {np.asarray(c.tokens)}")
        checked += 1
    return checked


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="pimref-100m", choices=list(ALL_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--engine", default="fused", choices=["fused", "loop"])
    ap.add_argument("--mode", default="batch", choices=["batch", "queue"])
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "pallas", "jnp"],
                    help="attention backend for every model family "
                    "(sets REPRO_ATTN_IMPL before programs are traced)")
    ap.add_argument("--kv-quant", default=None,
                    choices=["off", "int8", "int4", "auto"],
                    help="Proteus-quantized KV cache for the decode hot path "
                    "(sets REPRO_KV_QUANT before programs are traced)")
    ap.add_argument("--kv-page-size", type=int, default=None,
                    help="paged KV cache with this many tokens per page "
                    "(sets REPRO_KV_PAGES before programs are traced; "
                    "0 = contiguous per-slot cache)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="queue mode: give every request the same first N "
                    "tokens (exercises paged prefix sharing)")
    ap.add_argument("--spec-decode", default=None,
                    choices=["off", "ngram", "draft"],
                    help="speculative decoding drafter inside the fused scan "
                    "(sets REPRO_SPEC_DECODE before programs are traced)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft length per speculative iteration "
                    "(sets REPRO_SPEC_K)")
    ap.add_argument("--repeat-period", type=int, default=0,
                    help="queue mode: tile each prompt from a short period "
                    "(lookup-friendly workload for the n-gram drafter)")
    ap.add_argument("--spec-verify", action="store_true",
                    help="queue mode: re-drain the identical queue with "
                    "speculation forced off and assert byte-identical "
                    "completions (greedy identity gate)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="queue mode: bound the admission queue; submissions "
                    "beyond it get a queue_full error Completion")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="queue mode: per-request deadline; overdue requests "
                    "retire with a deadline error Completion")
    ap.add_argument("--page-pool-pages", type=int, default=None,
                    help="queue mode: physical page budget for the paged "
                    "cache pool (default slots * pages-per-slot)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="queue mode: arm seeded fault injection; plan comes "
                    "from REPRO_CHAOS (e.g. 'nan=1,slow=2,fail=1,pages=4') "
                    "or defaults to nan=1,slow=1,fail=1")
    ap.add_argument("--chaos-verify", action="store_true",
                    help="queue mode: re-drain the identical queue without "
                    "chaos and assert fault-free survivors are "
                    "byte-identical")
    ap.add_argument("--preempt-after", type=int, default=None,
                    help="queue mode: raise SIGTERM after N chunk dispatches "
                    "and checkpoint in-flight state to --snapshot "
                    "(exercises the real signal path)")
    ap.add_argument("--snapshot", default="serve_manifest.json",
                    help="manifest path written on preemption (SIGTERM or "
                    "--preempt-after)")
    ap.add_argument("--restore", default=None,
                    help="restore a serving manifest and drain the remaining "
                    "requests (implies queue mode)")
    ap.add_argument("--restore-verify", action="store_true",
                    help="with --restore: also run the original queue "
                    "uninterrupted and assert byte-identical completions")
    ap.add_argument("--shards", type=int, default=1,
                    help="queue mode: drain through a ServeFleet of this "
                    "many engine shards behind one dispatcher (1 = single "
                    "engine, no fleet)")
    ap.add_argument("--fleet-backend", default="inproc",
                    choices=["inproc", "mp"],
                    help="shard placement: in-process objects or "
                    "multiprocessing workers (the CPU multi-host stand-in)")
    ap.add_argument("--fleet-chaos", default=None,
                    help="shard-level fault plan, e.g. 'kill=1@2' (kill "
                    "shard 1 at fleet step 2), 'stall=0@3', 'drop=1@2x2', "
                    "or seeded budgets 'kills=1,seed=7' (implies --shards)")
    ap.add_argument("--fleet-verify", action="store_true",
                    help="re-drain the identical queue through one engine "
                    "and assert exactly one completion per request with "
                    "byte-identical survivor outputs")
    ap.add_argument("--heartbeat-timeout", type=float, default=120.0,
                    help="seconds a shard may owe its step reply before the "
                    "fleet counts a missed heartbeat")
    ap.add_argument("--miss-suspect", type=int, default=2,
                    help="consecutive missed heartbeats before a shard is "
                    "SUSPECT (no new routing)")
    ap.add_argument("--miss-dead", type=int, default=4,
                    help="consecutive missed heartbeats before a shard is "
                    "DEAD (failover)")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="fleet steps between periodic shard snapshots "
                    "(the failover replay source)")
    ap.add_argument("--manifest-dir", default=None,
                    help="persist each shard snapshot as an atomic "
                    "RestartManifest under this directory")
    ap.add_argument("--full", dest="smoke", action="store_false", default=True)
    ap.add_argument("--profile-dir", default=None,
                    help="record a profile of the run into this directory: "
                    "device ops named by program scope, host spans "
                    "repro.engine.{admit,prefill,chunk,sync,retire}")
    args = ap.parse_args()
    enable_compilation_cache()
    with tracing.profile(args.profile_dir):
        _run(args)


def _run(args) -> None:
    if args.attn_impl:
        os.environ["REPRO_ATTN_IMPL"] = args.attn_impl
    if args.kv_quant:
        os.environ["REPRO_KV_QUANT"] = args.kv_quant
    if args.kv_page_size is not None:
        os.environ["REPRO_KV_PAGES"] = str(args.kv_page_size)
    if args.spec_decode:
        os.environ["REPRO_SPEC_DECODE"] = args.spec_decode
    if args.spec_k is not None:
        os.environ["REPRO_SPEC_K"] = str(args.spec_k)
    if args.restore:
        eng = restore_serve(args.restore)
        _print_queue_stats(eng)
        if args.restore_verify:
            man = RestartManifest.load(args.restore)
            e, q = man.serve["engine"], man.serve["engine"].get("queue")
            assert q, "--restore-verify needs a manifest saved by queue mode"
            ref = serve_queue(
                e["arch"], smoke=e["smoke"], slots=e["slots"],
                requests=q["requests"], prompt_len=e["prompt_len"],
                gen=e["gen"], chunk=e["chunk"], seed=man.serve["seed"],
                temperature=man.serve["temperature"], top_k=e.get("top_k", 0),
                shared_prefix=q.get("shared_prefix", 0),
                repeat_period=q.get("repeat_period", 0))
            n = _assert_identical(eng, ref, "restore-verify")
            print(f"restore-verify: {n} completions byte-identical with an "
                  "uninterrupted drain")
        return
    if args.mode == "queue":
        chaos = ChaosConfig.from_env(args.chaos_seed)
        if chaos is None and args.chaos_seed is not None:
            chaos = ChaosConfig.parse("nan=1,slow=1,fail=1",
                                      seed=args.chaos_seed)
        handler = stop = None
        if args.preempt_after is not None:
            handler = PreemptionHandler().install()

            def stop(e, _h=handler):
                if (not _h.requested and
                        e.stats["decode_dispatches"] >= args.preempt_after):
                    os.kill(os.getpid(), signal.SIGTERM)
                return _h.requested

        queue_kw = dict(
            smoke=args.smoke, slots=args.slots, requests=args.requests,
            prompt_len=args.prompt_len, gen=args.gen, chunk=args.chunk,
            temperature=args.temperature, top_k=args.top_k,
            shared_prefix=args.shared_prefix,
            repeat_period=args.repeat_period)
        if args.shards > 1 or args.fleet_chaos or \
                args.fleet_backend == "mp":
            fc = (ShardChaosConfig.parse(args.fleet_chaos,
                                         seed=args.chaos_seed or 0)
                  if args.fleet_chaos else None)
            fleet = serve_fleet(
                args.arch, shards=max(args.shards, 1),
                backend=args.fleet_backend, fleet_chaos=fc,
                checkpoint_every=args.checkpoint_every,
                manifest_dir=args.manifest_dir,
                miss_suspect=args.miss_suspect, miss_dead=args.miss_dead,
                heartbeat_timeout_s=args.heartbeat_timeout, **queue_kw)
            _print_fleet_stats(fleet)
            try:
                if args.fleet_verify:
                    uids = sorted(c.uid for c in fleet.completions)
                    assert uids == list(range(args.requests)), (
                        f"fleet-verify: expected exactly one completion per "
                        f"request, got uids {uids}")
                    ref = serve_queue(args.arch, **queue_kw)
                    n = _assert_identical(fleet, ref, "fleet-verify")
                    print(f"fleet-verify: {n}/{args.requests} surviving "
                          f"completions byte-identical with a single-engine "
                          f"drain ({fleet.stats['failovers']} failovers)")
            finally:
                fleet.close()
            return
        eng = serve_queue(args.arch, max_queue=args.max_queue,
                          deadline_ms=args.deadline_ms, chaos=chaos,
                          page_pool_pages=args.page_pool_pages, stop=stop,
                          **queue_kw)
        _print_queue_stats(eng)
        if handler is not None:
            handler.uninstall()
            if handler.requested:
                save_serve_manifest(
                    args.snapshot, eng, arch=args.arch, smoke=args.smoke,
                    slots=args.slots, prompt_len=args.prompt_len,
                    gen=args.gen, chunk=args.chunk,
                    queue={"requests": args.requests,
                           "shared_prefix": args.shared_prefix,
                           "repeat_period": args.repeat_period})
                print(f"preempted after {eng.stats['decode_dispatches']} "
                      f"chunks: {len(eng.completions)}/{args.requests} done, "
                      f"manifest -> {args.snapshot}")
                return
        if args.chaos_verify and chaos is not None:
            ref = serve_queue(args.arch, **queue_kw)
            poisoned = {ev["uid"] for ev in eng.chaos_events
                        if ev["kind"] == "nan"}
            n = _assert_identical(eng, ref, "chaos-verify",
                                  skip_uids=poisoned)
            print(f"chaos-verify: {n}/{len(eng.completions)} fault-free "
                  f"survivors byte-identical "
                  f"({len(eng.chaos_events)} injected events)")
        if args.spec_verify and eng.spec != "off":
            ref = serve_queue(args.arch, spec="off", **queue_kw)
            n = _assert_identical(eng, ref, "spec-verify")
            print(f"spec-verify: {n} completions byte-identical with "
                  "speculation off")
        return
    out = serve(args.arch, smoke=args.smoke, batch=args.batch,
                prompt_len=args.prompt_len, gen=args.gen, chunk=args.chunk,
                engine=args.engine, temperature=args.temperature,
                top_k=args.top_k)
    print(f"engine={args.engine}  prefill: {out['prefill_s']:.3f}s  decode: "
          f"{out['decode_s_per_tok'] * 1e3:.1f}ms/tok  "
          f"throughput: {out['throughput_tok_s']:.1f} tok/s  "
          f"dispatches/token: {out['dispatches_per_token']:.3f}")
    if "accepted_len_per_draft" in out:
        print(f"spec accepted_len/draft: {out['accepted_len_per_draft']:.3f}")
    print("sample tokens:", out["tokens"][0][:10])


if __name__ == "__main__":
    main()
