"""Step builders: jit'd train / prefill / decode steps wired to the planner.

``make_train_step`` returns (step_fn, in_shardings, donate) ready for
``jax.jit``; the Proteus variant swaps the implicit cross-pod gradient
all-reduce for a quantized int8 reduction via a partial-manual shard_map over
the 'pod' axis (data/model stay GSPMD-auto) — hierarchical, narrow-value
aware, per DESIGN.md §2.3.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core import proteus
from repro.core.mimdram import Plan, plan_sharding, use_plan
from repro.launch import specs as specs_lib
from repro.launch import tracing
from repro.models import layers
from repro.models import module as mod
from repro.models.model import STAT_OVER_LAYERS
from repro.optim import Optimizer


def named(plan: Plan, pspec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(plan.mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------
def auto_microbatches(cfg: ModelConfig, shape: ShapeConfig, plan: Plan,
                      budget_bytes: float = 5e9) -> int:
    """Pick grad-accumulation factor so saved activations + logits fit.

    Rough per-device model: saved scan carries (B_loc*S*d*2B*L_saved) plus
    logits round-trip (B_loc*S*V_loc*6B)."""
    from repro.core.mimdram import _axis_size  # noqa: PLC0415

    if shape.mode != "train":
        return 1
    if cfg.microbatches_hint:
        return cfg.microbatches_hint
    dw = _axis_size(plan.mesh, plan.rules.get("act_batch")) or 1
    vw = _axis_size(plan.mesh, plan.rules.get("act_vocab")) or 1
    b_loc = max(shape.global_batch // dw, 1)
    saved = b_loc * shape.seq_len * cfg.d_model * 2 * max(cfg.num_layers, 1)
    logits = b_loc * shape.seq_len * (cfg.vocab_size / vw) * 6
    est = saved + logits
    nm = 1
    while est / nm > budget_bytes and nm < b_loc:
        nm *= 2
    return nm


def _value_and_grad(model, params, batch):
    """((loss, stats), grads): stats are the model's step counters where it
    gives them (``loss_and_stats``), else empty."""
    if hasattr(model, "loss_and_stats"):
        return jax.value_and_grad(model.loss_and_stats, has_aux=True)(
            params, batch)
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    return (loss, {}), grads


def _loss_and_grads(model, params, batch, nm: int):
    """(loss, grads, stats): value_and_grad with optional lax.scan gradient
    accumulation.

    Gradients are re-pinned to the parameter shardings (ZeRO-2: the data-axis
    reduce-scatter happens per layer inside the loop, not on a full-size
    unsharded stack afterwards)."""
    specs = model.param_specs()
    if nm <= 1:
        (loss, stats), grads = _value_and_grad(model, params, batch)
        return loss, mod.constrain_tree(grads, specs), stats

    def split(x):
        return x.reshape((nm, x.shape[0] // nm) + x.shape[1:])

    mb = jax.tree_util.tree_map(split, batch)
    # accumulate in fp32 unless the model trains in pure-bf16 params (1T-scale
    # memory budget; see configs/kimi_k2_1t.py)
    all_bf16 = all(l.dtype == jnp.bfloat16
                   for l in jax.tree_util.tree_leaves(params)
                   if jnp.issubdtype(l.dtype, jnp.floating))
    acc_dt = jnp.bfloat16 if all_bf16 else jnp.float32
    zero = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, acc_dt), params)

    def acc(carry, mbatch):
        lsum, gsum = carry
        (l, st), g = _value_and_grad(model, params, mbatch)
        g = mod.constrain_tree(g, specs)
        gsum = jax.tree_util.tree_map(
            lambda a, b: a + b.astype(a.dtype), gsum, g)
        return (lsum + l, mod.constrain_tree(gsum, specs)), st

    (loss, grads), stats = jax.lax.scan(
        acc, (jnp.zeros((), jnp.float32), zero), mb)
    inv = 1.0 / nm
    grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
    return loss * inv, grads, {k: STAT_OVER_LAYERS[k](v)
                               for k, v in stats.items()}


def make_train_step(model, optimizer: Optimizer, plan: Plan, run: RunConfig,
                    *, guard: bool = False, grad_hook=None):
    """Standard GSPMD train step (paper-faithful baseline distribution).

    ``guard=True`` arms the on-device non-finite guard: the step still
    computes gradients and the candidate update, but a non-finite loss or
    gradient norm selects the *old* params/opt_state for every output leaf.
    The select is ``jnp.where`` on the outputs, so buffer donation is
    preserved and a clean step is bitwise-identical to the unguarded step
    (a select with a true predicate is the identity). Guarded metrics are
    ``{"loss", "grad_norm", "skipped"}``, with the model's step counters
    beside them where it has any (``moe_rows``, ``moe_load_max`` of held
    experts: :meth:`TransformerLM.forward_and_stats`); the training loop
    counts consecutive ``skipped`` steps and aborts with
    ``TrainDivergedError`` — one bad batch costs one skipped step, never a
    poisoned parameter tree.

    ``grad_hook(loss, grads, arm) -> (loss, grads)`` is the chaos harness's
    trace-time injection point (see
    :func:`repro.distributed.chaos.nan_grad_hook`). When set, ``train_step``
    takes a trailing traced ``arm`` operand — the ``logits_hook`` pattern
    from ``make_generate_step`` — so one compiled program serves clean and
    poisoned dispatches, and a disarmed dispatch passes through
    bitwise-unchanged.
    """
    from repro.optim import global_norm  # noqa: PLC0415 (package re-export)

    nm = max(run.microbatches, 1)

    def train_step(params, opt_state, batch, arm=None):
        with use_plan(plan):
            loss, grads, stats = _loss_and_grads(model, params, batch, nm)
            if grad_hook is not None:
                loss, grads = grad_hook(loss, grads, arm)
            if not guard:
                with tracing.scope("optimizer"):
                    new_params, new_opt = optimizer.update(grads, opt_state,
                                                           params)
                return new_params, new_opt, {"loss": loss, **stats}
            with tracing.scope("guard"):
                gnorm = global_norm(grads)
                ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            with tracing.scope("optimizer"):
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)

            def sel(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o), new, old)

            metrics = {"loss": loss, "grad_norm": gnorm,
                       "skipped": jnp.logical_not(ok), **stats}
        with tracing.scope("guard"):
            return (sel(new_params, params), sel(new_opt, opt_state),
                    metrics)

    return train_step


def make_train_step_proteus(model, optimizer: Optimizer, plan: Plan,
                            run: RunConfig, pod_axis: str = "pod"):
    """Proteus train step: quantized cross-pod gradient reduction.

    Requires a multi-pod mesh; params are replicated across pods (pure DP on
    the pod axis), batch is pod-split. Inside the shard_map the 'data' and
    'model' axes remain auto (GSPMD), so intra-pod distribution is unchanged;
    only the slow inter-pod hop carries int8 payloads.
    """
    mesh = plan.mesh
    assert mesh is not None and pod_axis in mesh.shape, "needs a pod axis"
    n_pods = mesh.shape[pod_axis]
    # plan whose rules never touch the manual pod axis
    inner_rules = {
        k: (tuple(a for a in v if a != pod_axis) or None) if v else v
        for k, v in plan.rules.items()
    }
    inner_plan = Plan(rules=inner_rules, mesh=mesh, cfg=plan.cfg,
                      shape=plan.shape, notes=plan.notes + ("proteus-inner",))

    nm = max(run.microbatches, 1)

    def per_pod(params, opt_state, batch):
        with use_plan(inner_plan):
            loss, grads, _ = _loss_and_grads(model, params, batch, nm)
        grads = proteus.cross_pod_psum(
            grads, pod_axis, bits=run.proteus_grad_bits,
            block=run.proteus_block, mean=True, n_pods=n_pods)
        loss = jax.lax.pmean(loss, pod_axis)
        with use_plan(inner_plan), tracing.scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, {"loss": loss}

    def pod_spec_tree(tree, leading_pod: bool):
        return jax.tree_util.tree_map(
            lambda _: P(pod_axis) if leading_pod else P(), tree)

    def train_step(params, opt_state, batch):
        fn = shard_map(
            per_pod, mesh=mesh,
            in_specs=(pod_spec_tree(params, False),
                      pod_spec_tree(opt_state, False),
                      pod_spec_tree(batch, True)),
            out_specs=(pod_spec_tree(params, False),
                       pod_spec_tree(opt_state, False), {"loss": P()}),
            check_vma=False,
            axis_names=frozenset({pod_axis}))   # partial-manual: data/model stay auto
        return fn(params, opt_state, batch)

    return train_step


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------
def make_prefill_step(model, plan: Plan, max_len: Optional[int] = None,
                      full_logits: bool = False):
    """Prefill step; with ``max_len`` the returned cache is pre-sized for
    ``max_len`` total positions (no repad before decode). ``full_logits``
    returns logits for every position — the paged engine right-pads prompts
    to its bucket and reads the logits at each true prompt end."""
    def prefill_step(params, batch):
        with use_plan(plan):
            if max_len is None:
                return model.prefill(params, batch)
            return model.prefill(params, batch, max_len=max_len,
                                 full_logits=full_logits)
    return prefill_step


def make_decode_step(model, plan: Plan):
    def decode_step(params, cache, tokens):
        with use_plan(plan):
            return model.decode_step(params, cache, tokens)
    return decode_step


def logits_transform(logits: jax.Array, temperature: float,
                     top_k: int) -> jax.Array:
    """Temperature/top-k logits transform shared by the sampler and the
    speculative verifier's acceptance rule: fp32 scale by ``temperature``,
    then mask everything below the k-th highest logit to -1e30. Requires
    ``temperature > 0`` (greedy selection never calls this)."""
    lf = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jax.lax.top_k(lf, top_k)[0][..., -1:]
        lf = jnp.where(lf < kth, -1e30, lf)
    return lf


def sample_tokens(logits: jax.Array, key: jax.Array, temperature: float = 0.0,
                  top_k: int = 0) -> jax.Array:
    """On-device token selection. logits: (..., V) -> (...) int32.

    temperature <= 0 means greedy argmax (key unused); top_k > 0 restricts
    sampling to the k highest-probability tokens. Shape-generic over leading
    axes: the fused loop passes (B, V) rows, the speculative verifier passes
    a whole (B, k+1, V) block and gets one target per drafted position.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lf = logits_transform(logits, temperature, top_k)
    return jax.random.categorical(key, lf, axis=-1).astype(jnp.int32)


def spec_config(model, mode: Optional[str] = None,
                k: Optional[int] = None) -> Tuple[str, int]:
    """Effective ``(mode, k)`` for speculative decoding on ``model``.

    ``mode``/``k`` default to the ``REPRO_SPEC_DECODE`` / ``REPRO_SPEC_K``
    env knobs. Speculation is gated to the TransformerLM families
    (dense/moe/vlm) with full attention: recurrent/SSM/enc-dec decode paths
    have no multi-token verify step, and a sliding-window ring cache has no
    slack for the k-ahead speculative writes (the window would wrap onto
    rows the verify block still attends to). Unsupported combinations warn
    once and fall back to ``("off", 0)`` — serving keeps working.
    """
    from repro.kernels import common as kcommon

    mode = kcommon.spec_decode_mode() if mode is None else mode
    if mode not in kcommon.SPEC_DECODE_MODES:
        raise ValueError(f"spec mode {mode!r}: expected one of "
                         f"{kcommon.SPEC_DECODE_MODES}")
    if mode == "off":
        return "off", 0
    k = kcommon.spec_draft_len() if k is None else int(k)
    cfg = getattr(model, "cfg", None)
    fam = getattr(cfg, "family", None)
    if fam not in ("dense", "moe", "vlm"):
        warnings.warn(f"spec-decode {mode!r} unsupported for family {fam!r}; "
                      "falling back to off")
        return "off", 0
    if getattr(cfg, "attention_kind", "full") == "sliding":
        warnings.warn(f"spec-decode {mode!r} unsupported with sliding-window "
                      "attention; falling back to off")
        return "off", 0
    return mode, k


def ngram_draft(hist: jax.Array, hist_len: jax.Array, t0: jax.Array,
                k: int) -> jax.Array:
    """Device-side n-gram/prompt-lookup drafter: (B, k) draft tokens.

    ``hist`` (B, Hcap) holds each slot's committed prompt+emitted tokens
    (``hist_len`` valid, zero-padded); ``t0`` (B,) is the token about to be
    emitted. Finds the most recent prior occurrence of ``t0`` — preferring a
    bigram match where the preceding token also equals ``hist[len-1]`` — and
    drafts the k tokens that followed it. Matches are restricted to
    positions with at least one following token; a miss (or a continuation
    running past ``hist_len``) yields garbage drafts, which are SAFE: the
    verifier only commits tokens the target model confirms.
    """
    B, H = hist.shape
    b = jnp.arange(B, dtype=jnp.int32)
    pos = jnp.arange(H, dtype=jnp.int32)
    valid = pos[None, :] < (hist_len - 1)[:, None]        # continuation exists
    t_prev = hist[b, jnp.maximum(hist_len - 1, 0)]        # token before t0
    prev_col = jnp.concatenate(
        [jnp.full((B, 1), -1, hist.dtype), hist[:, :-1]], axis=1)
    uni = (hist == t0[:, None]) & valid
    bi = uni & (prev_col == t_prev[:, None])
    j_bi = jnp.max(jnp.where(bi, pos[None, :], -1), axis=1)
    j_uni = jnp.max(jnp.where(uni, pos[None, :], -1), axis=1)
    j = jnp.where(j_bi >= 0, j_bi, j_uni)                 # -1 on miss
    src = jnp.clip(j[:, None] + 1 + jnp.arange(k, dtype=jnp.int32)[None, :],
                   0, H - 1)
    return hist[b[:, None], src].astype(t0.dtype)


def make_serving_jits(model, plan: Plan, *, max_len: int, chunk: int,
                      temperature: float = 0.0, top_k: int = 0,
                      full_logits: bool = False,
                      spec: Optional[str] = None,
                      spec_k: Optional[int] = None,
                      logits_hook=None):
    """Sharding-pinned (prefill, generate, rep, cache_sh) for one serving cell.

    Cache (and fed-back token/key) shardings are pinned identically on both
    jits so prefill's cache has exactly the signature generate emits — each
    program compiles once; every chunk after the first is a compile-cache
    hit. With a mesh-less plan the pins are skipped (rep/cache_sh = None).

    ``spec``/``spec_k`` (default: the env knobs via :func:`spec_config`)
    select the speculative-decoding drafter. In a spec mode the cache is
    sized for ``max_len + spec_k`` positions — each verify block writes up to
    ``spec_k`` rows past the fed position, and the extra slack guarantees
    those k-ahead writes never wrap onto rows the block still attends to —
    and ``generate`` takes/returns the drafter history (see
    :func:`make_generate_step`), with the history buffers donated alongside
    the cache.

    ``logits_hook`` (see :func:`make_generate_step`) adds a trailing traced
    ``arm`` operand to ``generate`` — the chaos harness's NaN-injection
    point. The hook is trace-time only; arming is per-dispatch data, so one
    compiled program serves both poisoned and clean chunks.
    """
    spec, spec_k = spec_config(model, spec, spec_k)
    if plan.mesh is not None:
        rep = NamedSharding(plan.mesh, P())
        cache_sh = named(plan, specs_lib.cache_pspecs(model, plan))
    else:
        rep = cache_sh = None
    cache_len = max_len + (spec_k if spec != "off" else 0)
    prefill = jax.jit(make_prefill_step(model, plan, max_len=cache_len,
                                        full_logits=full_logits),
                      out_shardings=(None, cache_sh))
    gen_fn = make_generate_step(model, plan, chunk=chunk,
                                temperature=temperature, top_k=top_k,
                                spec=spec, spec_k=spec_k,
                                logits_hook=logits_hook)
    if spec == "off":
        generate = jax.jit(gen_fn, donate_argnums=(1,),
                           out_shardings=(cache_sh,) + (rep,) * 6)
    else:
        generate = jax.jit(gen_fn, donate_argnums=(1, 5, 6),
                           out_shardings=(cache_sh,) + (rep,) * 9)
    return prefill, generate, rep, cache_sh


def make_generate_step(model, plan: Plan, *, chunk: int,
                       temperature: float = 0.0, top_k: int = 0,
                       spec: str = "off", spec_k: int = 0,
                       logits_hook=None):
    """Fused decode loop: ``chunk`` iterations per dispatch via ``lax.scan``.

    The per-token serving loop pays one jit dispatch + one host sync per
    generated token; this rolls the whole decode loop (cache update, forward,
    sampling) into ONE on-device program. Jit it with ``donate_argnums=(1,)``
    so the cache is updated in place (no second live copy).

        generate_step(params, cache, tok, key, eos_id)
            -> (cache, tok, key, done, n_valid, toks, failed)

    ``tok`` (B, 1) is the next token to feed (from prefill argmax or the
    previous chunk); ``toks`` (B, chunk) are the emitted tokens, the first
    being ``tok`` itself — byte-identical to the per-token loop's output.

    EOS detection runs on device: ``eos_id`` is a traced int32 scalar (-1
    disables it; token ids are non-negative, so -1 never matches). The scan
    carries a per-slot ``done`` flag — once a slot emits EOS its sampled
    tokens are frozen (the EOS token is re-fed, so the tail of the chunk is
    deterministic) and ``n_valid`` (B,) counts the tokens up to and including
    EOS. The engine retires slots from ``(done, n_valid)`` without scanning
    token buffers on the host.

    ``failed`` (B,) is the on-device finite guard: True once a slot's logits
    go non-finite. Slots are independent through the whole decode stack, so
    the guard quarantines exactly the poisoned slot — its counting stops with
    the last token sampled from finite logits (already counted in
    ``n_valid``), its re-feed freezes like a done slot, and every other slot
    keeps decoding bit-identically. The engine retires failed slots with an
    error completion instead of poisoning the batch.

    ``logits_hook`` — ``hook(logits, row_pos, arm) -> logits`` with
    ``row_pos`` (B, S) the absolute cache position of each logits row — is
    the chaos harness's deterministic NaN-injection point, applied where a
    real model overflow would appear (before the guard and the sampler).
    When set, the jit takes a trailing traced ``arm`` (B,) int32 operand
    (poison position per slot, -1 disarmed) so one compiled program covers
    armed and clean dispatches.

    Speculative decoding (``spec="ngram"|"draft"``, draft length ``spec_k``)
    keeps the same chunked scan — still ONE dispatch per chunk — but each
    iteration drafts k tokens, runs one (k+1)-token verify block through
    ``decode_step`` (the multi-query shape the decode kernels already take),
    and commits only the leading drafts whose next-token targets confirm
    them, plus the model's own "bonus" token prediction after the last
    accepted draft. Rollback is positional: ``cache["pos"]`` rewinds to the
    committed length and the next iteration's (k+1)-row write window exactly
    covers the rejected rows before anything attends to them, so no KV data
    movement is needed for ring, paged, or quantized layouts. Signature
    grows the drafter history (``hist`` (B, Hcap) committed prompt+output
    tokens, ``hist_len`` (B,)) and the per-iteration accept counts:

        generate_step(params, cache, tok, key, eos_id, hist, hist_len)
            -> (cache, tok, key, done, n_valid, toks, hist, hist_len, acc,
                failed)

    ``toks`` is a compacted (B, chunk*(k+1)) buffer — the first ``n_valid``
    entries per row are the emitted tokens, so callers consume it exactly
    like the non-spec (B, chunk) buffer. ``acc`` (B, chunk) is the number of
    tokens committed by each iteration (1..k+1; -1 for already-done slots) —
    the engine's accepted-length histogram. Greedy (temperature <= 0) output
    is byte-identical to ``spec="off"``; sampled speculation draws the
    (k+1)-position targets from one key split per iteration via the shared
    :func:`sample_tokens`, which is distribution-exact for a deterministic
    drafter but follows a different key schedule than the per-token loop.
    """
    if spec == "off":
        def generate_step(params, cache, tok, key, eos_id, arm=None):
            with use_plan(plan):
                B = tok.shape[0]

                def body(carry, _):
                    cache, tok, key, done, n_valid, failed = carry
                    emitted = tok[:, 0]
                    done_now = done | (emitted == eos_id)
                    n_valid = n_valid + jnp.where(done | failed, 0,
                                                  1).astype(jnp.int32)
                    pos0 = cache["pos"]
                    logits, cache = model.decode_step(params, cache, tok)
                    if logits_hook is not None:
                        logits = logits_hook(logits, pos0[:, None], arm)
                    fin = layers.slot_isfinite(logits)
                    failed_now = failed | (~fin & ~done_now)
                    key, sub = jax.random.split(key)
                    nxt = sample_tokens(logits[:, -1], sub, temperature, top_k)
                    nxt = jnp.where(done_now | failed_now, emitted, nxt)
                    return (cache, nxt[:, None], key, done_now, n_valid,
                            failed_now), emitted

                done0 = jnp.zeros((B,), bool)
                n0 = jnp.zeros((B,), jnp.int32)
                f0 = jnp.zeros((B,), bool)
                (cache, tok, key, done, n_valid, failed), toks = jax.lax.scan(
                    body, (cache, tok, key, done0, n0, f0), None, length=chunk)
            return (cache, tok, key, done, n_valid, toks.T,
                    failed)                                 # toks: (B, chunk)
        return generate_step

    k = int(spec_k)
    span = k + 1
    if spec == "draft":
        from repro.kernels import common as kcommon
        n_draft_layers = (kcommon.spec_draft_layers()
                          or max(1, model.cfg.num_layers // 2))
        n_draft_layers = min(n_draft_layers, model.cfg.num_layers)

    def generate_step(params, cache, tok, key, eos_id, hist, hist_len,
                      arm=None):
        with use_plan(plan):
            B = tok.shape[0]
            Hcap = hist.shape[1]
            Lbuf = chunk * span
            b = jnp.arange(B, dtype=jnp.int32)
            idx = jnp.arange(span, dtype=jnp.int32)

            def body(carry, _):
                (cache, tok, key, done, n_valid, failed, hist, hist_len,
                 toks) = carry
                t0 = tok[:, 0]
                if spec == "ngram":
                    drafts = ngram_draft(hist, hist_len, t0, k)
                else:
                    # layer-skip self-drafting: k greedy single-token steps
                    # through the first n_draft_layers of the target itself,
                    # scribbling scratch KV the verify block overwrites.
                    pos_in = cache["pos"]

                    def dbody(dcarry, _):
                        dc, dt = dcarry
                        dlogits, dc = model.decode_step(
                            params, dc, dt, layers=n_draft_layers)
                        nt = jnp.argmax(dlogits[:, -1], axis=-1)
                        nt = nt.astype(t0.dtype)
                        return (dc, nt[:, None]), nt

                    (cache, _), dr = jax.lax.scan(
                        dbody, (cache, tok), None, length=k)
                    cache = dict(cache, pos=pos_in)
                    drafts = dr.T
                blk = jnp.concatenate([tok, drafts], axis=1)   # (B, k+1)
                pos0 = cache["pos"]
                logits, cache = model.decode_step(params, cache, blk)
                if logits_hook is not None:
                    row_pos = pos0[:, None] + idx[None, :]
                    logits = logits_hook(logits, row_pos, arm)
                # per-row finite guard: row j's logits are the target for
                # draft j+1 and the bonus after j accepts. Acceptance stops
                # at the last finite target, so every committed token derives
                # from finite logits; the iteration whose bonus row is the
                # non-finite one quarantines the slot — exactly matching the
                # non-spec loop's "last token counted, next token lost".
                fin_row = jnp.isfinite(logits).all(axis=-1)    # (B, span)
                key, sub = jax.random.split(key)
                tgt = sample_tokens(logits, sub, temperature, top_k)
                ok = (blk[:, 1:] == tgt[:, :-1]).astype(jnp.int32)
                ok = ok * fin_row[:, :-1].astype(jnp.int32)
                a = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)   # 0..k accepted
                # commit blk[:, :a+1], truncated at the first EOS (inclusive)
                is_eos = blk == eos_id
                eos_hit = is_eos & (idx[None, :] <= a[:, None])
                any_eos = eos_hit.any(axis=1)
                first_eos = jnp.min(
                    jnp.where(eos_hit, idx[None, :], span), axis=1)
                cnt = jnp.where(any_eos, first_eos + 1, a + 1)
                cnt = jnp.where(done | failed, 0, cnt).astype(jnp.int32)
                bonus_fin = jnp.take_along_axis(
                    fin_row, a[:, None], axis=1)[:, 0]
                failed_now = failed | (~bonus_fin & ~any_eos & ~done)
                # rollback = positional rewind: the next iteration's k+1-row
                # write window starts at pos0+cnt, covering every rejected row
                # before anything attends to it (done slots advance 1, like
                # the non-spec loop's frozen re-feed).
                adv = jnp.maximum(cnt, 1).astype(pos0.dtype)
                cache = dict(cache, pos=pos0 + adv)
                bonus = tgt[b, a]
                nxt = jnp.where(any_eos, jnp.asarray(eos_id, t0.dtype), bonus)
                nxt = jnp.where(done | failed_now, t0, nxt)    # freeze re-feed
                wv = idx[None, :] < cnt[:, None]
                tslot = jnp.where(wv, n_valid[:, None] + idx[None, :], Lbuf)
                toks = toks.at[b[:, None], tslot].set(blk, mode="drop")
                hslot = jnp.where(wv, hist_len[:, None] + idx[None, :], Hcap)
                hist = hist.at[b[:, None], hslot].set(
                    blk.astype(hist.dtype), mode="drop")
                acc_i = jnp.where(done | failed, -1, cnt)
                return (cache, nxt[:, None], key, done | any_eos,
                        n_valid + cnt, failed_now, hist, hist_len + cnt,
                        toks), acc_i

            done0 = jnp.zeros((B,), bool)
            n0 = jnp.zeros((B,), jnp.int32)
            f0 = jnp.zeros((B,), bool)
            toks0 = jnp.zeros((B, Lbuf), tok.dtype)
            carry0 = (cache, tok, key, done0, n0, f0, hist, hist_len, toks0)
            (cache, tok, key, done, n_valid, failed, hist, hist_len,
             toks), acc = jax.lax.scan(body, carry0, None, length=chunk)
        return (cache, tok, key, done, n_valid, toks, hist, hist_len,
                acc.T, failed)                                 # acc: (B, chunk)
    return generate_step


# ---------------------------------------------------------------------------
# Assembly for one cell: abstract inputs + shardings (dry-run / launcher)
# ---------------------------------------------------------------------------
def cell_artifacts(cfg: ModelConfig, shape: ShapeConfig, plan: Plan,
                   run: RunConfig, optimizer_name: Optional[str] = None):
    """Returns (model, step_fn, abstract_args, in_shardings, donate, run)."""
    from repro.models import build_model
    from repro.optim import make_optimizer

    model = build_model(cfg)
    pspecs = mod.param_pspecs(model.param_specs(), plan)
    abstract_p = mod.abstract_params(model.param_specs())
    batch = specs_lib.input_specs(cfg, shape)
    batch_ps = specs_lib.batch_pspecs(cfg, shape, plan)

    if shape.mode == "train":
        if run.microbatches == 0:
            run = run.replace(microbatches=auto_microbatches(cfg, shape, plan))
        opt = make_optimizer(optimizer_name or cfg.optimizer, run)
        ostate_specs = opt.state_specs(model.param_specs())
        abstract_o = mod.abstract_params(ostate_specs)
        opt_ps = mod.param_pspecs(ostate_specs, plan)
        if run.proteus_enabled and plan.mesh is not None and \
                "pod" in plan.mesh.shape:
            step = make_train_step_proteus(model, opt, plan, run)
        else:
            step = make_train_step(model, opt, plan, run)
        args = (abstract_p, abstract_o, batch)
        shardings = (named(plan, pspecs), named(plan, opt_ps),
                     named(plan, batch_ps))
        return model, step, args, shardings, (0, 1), run, None

    if shape.mode == "prefill":
        step = make_prefill_step(model, plan)
        args = (abstract_p, batch)
        shardings = (named(plan, pspecs), named(plan, batch_ps))
        # pin the returned cache to the serving cache layout (otherwise the
        # scan ys inherit activation sharding and the cache lands 16x fatter)
        cache_out = named(plan, specs_lib.cache_pspecs(model, plan, shape))
        out_sh = (None, cache_out)
        return model, step, args, shardings, (), run, out_sh

    # decode
    step = make_decode_step(model, plan)
    cache = specs_lib.cache_specs(model, shape)
    cache_ps = specs_lib.cache_pspecs(model, plan, shape)
    args = (abstract_p, cache, batch["tokens"])
    shardings = (named(plan, pspecs), named(plan, cache_ps),
                 NamedSharding(plan.mesh, batch_ps["tokens"])
                 if plan.mesh is not None else None)
    out_sh = (None, named(plan, cache_ps))
    return model, step, args, shardings, (1,), run, out_sh
