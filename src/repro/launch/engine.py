"""Continuous-batching serving engine: slot-based queue over fused decode.

The DaPPA-style contract applied to serving: callers submit :class:`Request`s
and get :class:`Completion`s back — they never manage hardware slots, caches,
padding, or dispatch. Internally the engine keeps a fixed number of *slots*
(rows of one batched, pre-sized KV cache). Between fused decode chunks
(``make_generate_step``: one jit dispatch per ``chunk`` tokens) finished
sequences are swapped out and queued prompts are prefilled into the freed
slots. Every per-slot state (``pos``, ``pos_ids``, KV rows) is independent,
so sequences at different depths coexist in one cache.

With ``REPRO_KV_PAGES=<n>`` the KV cache is *paged*: fixed-size pages live in
one shared pool per leaf and each slot holds an int32 page table. A host-side
free-list allocator hands out pool rows on prefill and reclaims them on
retirement, so HBM committed to KV scales with tokens actually held, not with
``slots * max_len`` (the statically over-allocated layout the paper's MIMDRAM
line attacks in DRAM). Full prefill pages are hash-consed across slots
(prefix sharing, refcounted, copy-on-write before any divergent write), and
physical page 0 is a reserved trash page: retired slots point there, so their
stale in-flight decode writes land harmlessly.

All device programs have static shapes (slots x prompt_len x max_len x
chunk), so after the first chunk per shape everything is a compile-cache hit.

Fault tolerance (the Proteus runtime-engine contract — adapt, don't crash):
admission control reserves worst-case KV pages per request up front so
demand allocation/COW can never exhaust the pool mid-decode; a bounded
frontend queue rejects overflow with ``queue_full`` completions; per-request
deadlines retire expired work; an on-device finite guard in the fused scan
quarantines exactly the slot whose logits went non-finite; transient
pre-dispatch failures retry with backoff; a StragglerMonitor watchdog on the
chunk dispatch sheds load (speculation off, then smaller chunks) under
sustained pressure; and ``snapshot()``/``load_snapshot()`` round-trip the
queue + per-slot progress through a ``RestartManifest`` for
preemption-safe serving. Every submitted request ends in exactly one
:class:`Completion` — success or a typed error ``reason``.

    PYTHONPATH=src python -m repro.launch.serve --mode queue --arch pimref-100m
"""
from __future__ import annotations

import bisect
import enum
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig
from repro.core.mimdram import Plan
from repro.distributed.chaos import (ChaosConfig, ChaosMonkey,
                                     TransientStepError, nan_logits_hook)
from repro.distributed.fault_tolerance import StragglerMonitor
from repro.kernels.common import kv_page_size
from repro.launch import specs as specs_lib
from repro.launch import tracing
from repro.launch.steps import (make_generate_step, make_serving_jits,
                                spec_config)
from repro.models.layers import PagedKVCache, QKVCache


# chunk wall times are kept as counts over fixed log-spaced buckets, ten a
# decade from 10 us to 1000 s (bucket i holds times in [EDGES[i-1],
# EDGES[i]); the first and last are open), so the record stays the same size
# for the engine's whole life; each chunk's own time is its
# ``repro.engine.chunk`` and ``repro.engine.sync`` spans in a profile
CHUNK_HIST_EDGES = tuple(1e-5 * 10 ** (i / 10) for i in range(81))


def chunk_hist() -> List[int]:
    return [0] * (len(CHUNK_HIST_EDGES) + 1)


def hist_quantile(counts: List[int], q: float) -> float:
    """The ``q`` quantile (0..1) of a :data:`CHUNK_HIST_EDGES` histogram, as
    its bucket's geometric middle (within 12% of the true value); 0 when
    empty."""
    n = sum(counts)
    if n == 0:
        return 0.0
    rank, seen = max(q * n, 1.0), 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= rank:
            break
    edges = CHUNK_HIST_EDGES
    lo = edges[max(i - 1, 0)]
    hi = edges[min(i, len(edges) - 1)]
    return (lo * hi) ** 0.5


class ErrorReason(str, enum.Enum):
    """Typed ``Completion.reason`` values — the engine's failure model.

    Shared by the engine, the serve CLI, and the bench columns; see the
    README "Robust serving" table for which fault maps to which reason.
    """

    PROMPT_TOO_LONG = "prompt_too_long"   # prompt exceeds the engine bucket
    BAD_REQUEST = "bad_request"           # empty prompt / malformed extras
    QUEUE_FULL = "queue_full"             # bounded frontend queue overflow
    DEADLINE = "deadline"                 # per-request deadline expired
    PAGE_POOL = "page_pool"               # KV page pool cannot hold request
    NAN_LOGITS = "nan_logits"             # finite guard quarantined the slot
    STEP_FAILURE = "step_failure"         # chunk dispatch failed (post-retry)
    SHARD_LOST = "shard_lost"             # fleet shard died, replay impossible

    def __str__(self) -> str:             # log/CSV-friendly
        return self.value


@dataclass
class Request:
    """One generation request. ``tokens``: 1-D int32 prompt; prompts longer
    than the engine's prompt bucket are rejected with an ``error`` completion
    (never silently truncated), shorter ones are padded to the bucket.
    ``extras``: additional prefill inputs (e.g. ``patch_embeds``) shaped for
    batch=1 at the engine's prompt length. ``deadline_ms``: wall-clock budget
    from submission; expiry retires the request with a ``deadline`` error
    completion carrying whatever tokens were produced."""

    uid: int
    tokens: np.ndarray
    max_new_tokens: int
    extras: Optional[Dict[str, Any]] = None
    deadline_ms: Optional[float] = None


@dataclass
class Completion:
    uid: int
    tokens: np.ndarray            # generated token ids (1-D; may be partial
                                  # on error — e.g. deadline/nan quarantine)
    finish_reason: str            # "length" | "eos" | "error"
    error: Optional[str] = None   # set when finish_reason == "error"
    reason: Optional[str] = None  # ErrorReason value when error, else None


@dataclass
class _Slot:
    request: Request
    produced: List[int] = field(default_factory=list)
    n: int = 0                    # true prompt length (paged mode)
    cap: int = 0                  # per-request generation cap
    chunks: int = 0               # decode chunks dispatched since insert


class PromptTooLongError(ValueError):
    """Prompt exceeds the engine's prompt bucket (no silent truncation)."""


class PagePoolExhaustedError(RuntimeError):
    """KV page pool has no free physical page.

    With admission reservation this is defense-in-depth: the engine only
    admits a request when its worst-case page demand fits alongside every
    active slot's reservation, so only external pressure (the chaos
    harness stealing pages, or an allocator bug) can trigger it. The engine
    catches it and retires the offending request with a ``page_pool`` error
    completion; other slots keep draining.
    """

    def __init__(self, alloc: "_PageAllocator", what: str):
        self.pool_stats = {
            "n_phys": alloc.n_phys, "free": len(alloc.free),
            "used": alloc.used, "registered": len(alloc.registry),
        }
        super().__init__(
            f"KV page pool exhausted during {what}: "
            f"{self.pool_stats['used']}/{alloc.n_phys - 1} pages in use, "
            f"{self.pool_stats['free']} free, "
            f"{self.pool_stats['registered']} prefix-registered")


class _PageAllocator:
    """Host-side free-list allocator over the physical page pool.

    Pool row 0 is the trash page and is never handed out. ``refs`` counts how
    many slot-table entries point at each physical page; ``registry`` is the
    hash-cons map for prefix sharing: (logical page index, prefix-token
    bytes) -> physical page. Registered pages are freed (and unregistered)
    when their last reference drops — sharing is across *concurrent* slots.
    """

    def __init__(self, n_phys: int):
        self.n_phys = n_phys
        self.free: List[int] = list(range(n_phys - 1, 0, -1))
        self.refs = np.zeros(n_phys, np.int32)
        self.registry: Dict[Tuple[int, bytes], int] = {}
        self.reg_key: Dict[int, Tuple[int, bytes]] = {}
        self.hits = 0

    def alloc(self, what: str = "alloc") -> int:
        if not self.free:
            raise PagePoolExhaustedError(self, what)
        phys = self.free.pop()
        self.refs[phys] = 1
        return phys

    def lookup(self, key: Tuple[int, bytes]) -> Optional[int]:
        phys = self.registry.get(key)
        if phys is not None:
            self.refs[phys] += 1
            self.hits += 1
        return phys

    def register(self, phys: int, key: Tuple[int, bytes]) -> None:
        self.registry[key] = phys
        self.reg_key[phys] = key

    def unregister(self, phys: int) -> None:
        key = self.reg_key.pop(phys, None)
        if key is not None:
            self.registry.pop(key, None)

    def decref(self, phys: int) -> None:
        self.refs[phys] -= 1
        if self.refs[phys] == 0:
            self.unregister(phys)
            self.free.append(phys)

    @property
    def used(self) -> int:
        return int((self.refs > 0).sum())


class ServeEngine:
    """Slot-based continuous batching over one fused-decode compiled program.

    Args:
      slots: number of concurrently decoded sequences (cache batch dim).
      prompt_len: prompt bucket; prompts are padded to this (left-padded in
        the contiguous layout, right-padded with true-length tracking in the
        paged layout) and rejected when longer.
      max_new: per-request generation cap (and cache sizing: max_len defaults
        to prompt_len + max_new).
      chunk: decode tokens per dispatch (the fused scan length).
      eos_id: stop token (None = length-only stopping).
      temperature/top_k: sampling knobs (0 temperature = greedy).
      spec/spec_k: speculative-decoding drafter ("off"|"ngram"|"draft") and
        draft length (default: the REPRO_SPEC_DECODE / REPRO_SPEC_K knobs).
        Transparent to callers — greedy completions are byte-identical with
        speculation on or off; stats gain spec_accepted_len_per_draft and a
        spec_accept_hist accepted-length histogram.
      max_queue: bound on the *waiting* queue (active slots are separate);
        submissions past it complete immediately with a ``queue_full`` error.
        None = unbounded (the pre-robustness behavior).
      deadline_ms: default wall-clock budget applied to requests that do not
        carry their own ``Request.deadline_ms``. None = no deadline.
      page_pool_pages: physical KV pages in the paged pool (default
        ``slots * n_logical_pages``, the worst case — admission then never
        blocks on pages). Smaller pools make the page-reservation admission
        control load-bearing: requests wait until their worst-case page
        demand fits alongside every active slot's reservation.
      chaos: a :class:`~repro.distributed.chaos.ChaosConfig` arming the
        deterministic fault-injection harness for this engine.
      max_retries/retry_backoff_s: chunk-level retry budget for transient
        pre-dispatch failures (a retry never replays a dispatch whose
        donated operands are consumed; real dispatch exceptions fail over to
        ``step_failure`` completions for everything in flight).
      straggler_threshold/shed_after: the chunk-dispatch watchdog —
        chunks slower than ``threshold x`` the wall-time EMA are straggler
        events, and ``shed_after`` *consecutive* events shed load one level
        (speculation off, then chunk halved). Greedy output is
        byte-identical across shed levels, so shedding is invisible except
        in latency and ``stats``.
      clock: monotonic-seconds callable for deadlines (tests inject a fake).
    """

    def __init__(self, model, params, plan: Plan, *, slots: int = 4,
                 prompt_len: int = 32, max_new: int = 32, chunk: int = 8,
                 max_len: Optional[int] = None, eos_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 spec: Optional[str] = None, spec_k: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 page_pool_pages: Optional[int] = None,
                 chaos: Optional[ChaosConfig] = None,
                 max_retries: int = 2, retry_backoff_s: float = 0.02,
                 straggler_threshold: float = 3.0, shed_after: int = 2,
                 clock: Optional[Callable[[], float]] = None):
        self.model, self.params, self.plan = model, params, plan
        self.slots, self.prompt_len, self.chunk = slots, prompt_len, chunk
        self.max_new, self.eos_id = max_new, eos_id
        self.max_len = max_len or (prompt_len + max_new)
        assert self.max_len >= prompt_len + 1
        self.max_queue, self.deadline_ms = max_queue, deadline_ms
        self.max_retries, self.retry_backoff_s = max_retries, retry_backoff_s
        self.shed_after = shed_after
        self._clock = clock or time.monotonic
        self._chaos = ChaosMonkey(chaos) if chaos is not None else None
        self._straggler = StragglerMonitor(threshold=straggler_threshold,
                                           warmup_steps=3)
        self.seed = seed
        self._temperature, self._top_k = temperature, top_k
        self._dead = False
        # speculative decoding: each fused-scan iteration verifies a
        # (spec_k+1)-token block, so a chunk can write chunk*(spec_k+1)
        # positions and the cache carries spec_k rows of k-ahead slack
        self.spec, self.spec_k = spec_config(model, spec, spec_k)
        self.chunk_span = chunk * (self.spec_k + 1) \
            if self.spec != "off" else chunk

        # big cache = batch-1 prefill cache zeros, tiled to `slots` rows
        shape1 = ShapeConfig("engine_prefill", seq_len=prompt_len,
                             global_batch=1, mode="prefill")
        small = specs_lib.prefill_cache_specs(
            model, model.cfg, shape1,
            self.max_len + (self.spec_k if self.spec != "off" else 0))
        paged_leaves = [l for l in jax.tree_util.tree_leaves(
            small, is_leaf=lambda x: isinstance(x, PagedKVCache))
            if isinstance(l, PagedKVCache)]
        self.paged = kv_page_size() > 0 and bool(paged_leaves)
        if self.paged:
            self.page_size = paged_leaves[0].page_size
            self.n_logical_pages = paged_leaves[0].table.shape[-1]
            self.cache_pos_len = self.page_size * self.n_logical_pages
            assert all(l.page_size == self.page_size
                       and l.table.shape[-1] == self.n_logical_pages
                       for l in paged_leaves), (
                "paged engine needs one shared (page_size, n_pages) across "
                "all paged cache leaves")
            # +1: physical page 0 is the reserved trash page
            self.n_phys_pages = (slots * self.n_logical_pages
                                 if page_pool_pages is None
                                 else int(page_pool_pages)) + 1

        # chaos NaN injection compiles a logits hook into the fused scan;
        # arming is per-dispatch data (arm[slot] = poison position, -1 =
        # disarmed), so clean dispatches stay bitwise-identical
        self._hook = (nan_logits_hook if self._chaos is not None
                      and self._chaos.cfg.wants_nan else None)
        self._prefill, self._generate, rep, cache_sh = make_serving_jits(
            model, plan, max_len=self.max_len, chunk=chunk,
            temperature=temperature, top_k=top_k, full_logits=self.paged,
            spec=self.spec, spec_k=self.spec_k, logits_hook=self._hook)
        self._rep, self._cache_sh = rep, cache_sh
        self._arm_np = np.full((slots,), -1, np.int32)
        # load shedding swaps in degraded generate programs (built lazily);
        # self._generate stays the warmed level-0 program
        self._spec_live, self._chunk_live = self.spec, chunk
        self._gen_shed = None
        # family-aware prefill inputs: vlm reserves a patch prefix inside the
        # prompt bucket (shorter token field), audio needs src_embeds, etc.
        self._batch_template = specs_lib.input_specs(model.cfg, shape1)
        self._tok_len = self._batch_template["tokens"].shape[1]
        self._prefix_len = (self.prompt_len - self._tok_len
                            if model.cfg.family == "vlm" else 0)
        axes = model.cache_logical_axes()
        # -1 = no batch axis (leaf shared across slots; None breaks tree_map);
        # the string "paged" marks whole PagedKVCache leaves, which get pool
        # scatters + table-row writes instead of batch-row slicing.
        is_node = lambda x: isinstance(x, (tuple, PagedKVCache))
        self._batch_axis = jax.tree_util.tree_map(
            lambda ax: "paged" if isinstance(ax, PagedKVCache)
            else (ax.index("act_batch") if "act_batch" in ax else -1),
            axes, is_leaf=is_node)
        is_marked = lambda x: isinstance(x, (tuple, str)) or (
            isinstance(x, int) and not isinstance(x, bool))

        def tile(ax, sd):
            if isinstance(ax, str):          # paged: widen pool, zero tables
                n_phys = self.n_phys_pages

                def z(s, nd):
                    shp = list(s.shape)
                    shp[len(shp) - nd] = n_phys
                    return jnp.zeros(tuple(shp), s.dtype)

                pages = (QKVCache(z(sd.pages.codes, 4), z(sd.pages.scale, 3))
                         if isinstance(sd.pages, QKVCache)
                         else z(sd.pages, 4))
                tshp = list(sd.table.shape)
                tshp[-2] = slots
                return PagedKVCache(pages, jnp.zeros(tuple(tshp), jnp.int32))
            shp = list(sd.shape)
            if ax >= 0:
                shp[ax] = slots
            return jnp.zeros(tuple(shp), sd.dtype)

        self.cache = jax.tree_util.tree_map(tile, self._batch_axis, small,
                                            is_leaf=is_marked)
        self._tok = jnp.zeros((slots, 1), jnp.int32)
        self._key = jax.random.PRNGKey(seed)
        if self.spec != "off":
            # n-gram drafter history: committed prompt+emitted tokens per
            # slot, sized for the bucket + cap + within-chunk overshoot
            self.hist_cap = self._tok_len + self.max_new + self.chunk_span
            self._hist = jnp.zeros((slots, self.hist_cap), jnp.int32)
            self._hist_len = jnp.zeros((slots,), jnp.int32)
        if rep is not None:
            self.cache = jax.device_put(self.cache, cache_sh)
            self._tok = jax.device_put(self._tok, rep)
            self._key = jax.device_put(self._key, rep)
            if self.spec != "off":
                self._hist = jax.device_put(self._hist, rep)
                self._hist_len = jax.device_put(self._hist_len, rep)

        def pool_idx(bp, nd):
            # page axis sits nd-from-the-end: -4 for (.., P, ps, H, D) pools
            # and codes, -3 for (.., P, ps, H) scale pools
            return bp.ndim - nd

        def insert(big, tok, small_cache, first_tok, slot, dest_rows,
                   table_row, pos_val, hist=None, hist_len=None,
                   tok_row=None, n_tok=None):
            def put(ax, b, s):
                if isinstance(ax, str):      # paged leaf
                    def pp(bp, sp, nd):
                        a = pool_idx(bp, nd)
                        src = sp[(slice(None),) * a + (slice(1, None),)]
                        return bp.at[(slice(None),) * a + (dest_rows,)].set(
                            src.astype(bp.dtype))

                    pages = (QKVCache(pp(b.pages.codes, s.pages.codes, 4),
                                      pp(b.pages.scale, s.pages.scale, 3))
                             if isinstance(b.pages, QKVCache)
                             else pp(b.pages, s.pages, 4))
                    table = b.table.at[..., slot, :].set(table_row)
                    return PagedKVCache(pages, table)
                if ax < 0:
                    return b
                start = tuple(slot if j == ax else 0 for j in range(b.ndim))
                return jax.lax.dynamic_update_slice(
                    b, s.astype(b.dtype), start)

            big = jax.tree_util.tree_map(put, self._batch_axis, big,
                                         small_cache, is_leaf=is_marked)
            if self.paged and "pos" in big:
                # right-padded bucket prefill: decode resumes at the true
                # prompt end, not at the bucket length
                big["pos"] = big["pos"].at[slot].set(pos_val)
            tok = jax.lax.dynamic_update_slice(tok, first_tok, (slot, 0))
            if hist is None:
                return big, tok
            # seed the n-gram drafter from the prefill tokens already on
            # device (no extra host copy): rotate left-padded prompts so the
            # true tokens sit at hist[:n_tok], zero the stale tail
            row = tok_row[0].astype(jnp.int32)
            if not self.paged:               # left-padded contiguous bucket
                row = jnp.roll(row, n_tok - row.shape[0])
            full = jnp.zeros((self.hist_cap,), jnp.int32)
            full = full.at[:row.shape[0]].set(row)
            hist = jax.lax.dynamic_update_slice(hist, full[None, :], (slot, 0))
            hist_len = hist_len.at[slot].set(n_tok)
            return big, tok, hist, hist_len

        if self.spec != "off":
            self._insert = jax.jit(insert, donate_argnums=(0, 1, 8, 9),
                                   out_shardings=(cache_sh, rep, rep, rep))
        else:
            self._insert = jax.jit(insert, donate_argnums=(0, 1),
                                   out_shardings=(cache_sh, rep))

        if self.paged:
            def clear_slot(big, slot):
                def cl(ax, b):
                    if isinstance(ax, str):
                        return PagedKVCache(
                            b.pages, b.table.at[..., slot, :].set(0))
                    return b
                return jax.tree_util.tree_map(cl, self._batch_axis, big,
                                              is_leaf=is_marked)

            def cow(big, slot, logical_i, old_row, new_row):
                def c(ax, b):
                    if not isinstance(ax, str):
                        return b

                    def cp(bp, nd):
                        a = pool_idx(bp, nd)
                        row = jax.lax.dynamic_index_in_dim(
                            bp, old_row, axis=a, keepdims=False)
                        return bp.at[(slice(None),) * a + (new_row,)].set(row)

                    pages = (QKVCache(cp(b.pages.codes, 4),
                                      cp(b.pages.scale, 3))
                             if isinstance(b.pages, QKVCache)
                             else cp(b.pages, 4))
                    return PagedKVCache(
                        pages, b.table.at[..., slot, logical_i].set(new_row))
                return jax.tree_util.tree_map(c, self._batch_axis, big,
                                              is_leaf=is_marked)

            self._clear_slot = jax.jit(clear_slot, donate_argnums=(0,),
                                       out_shardings=cache_sh)
            self._cow = jax.jit(cow, donate_argnums=(0,),
                                out_shardings=cache_sh)
            self._alloc = _PageAllocator(self.n_phys_pages)
            self._host_table = np.zeros((slots, self.n_logical_pages),
                                        np.int32)
            # prefix sharing needs (a) pure-token prompts (patch/src extras
            # are not in the hash key) and (b) a cache long enough that the
            # bucket prefill never ring-wraps (page <-> position identity)
            self._share_ok = (set(self._batch_template) == {"tokens"}
                              and self.cache_pos_len >= self.prompt_len)

        self._queue: Deque[Request] = deque()
        self._active: Dict[int, _Slot] = {}
        self._free: List[int] = list(range(slots))[::-1]
        self.completions: List[Completion] = []
        # admission reservation: worst-case pages per active slot; the sum
        # never exceeds the usable pool, so demand alloc/COW cannot exhaust
        self._reserved: Dict[int, int] = {}
        self._reserved_total = 0
        self._deadline_at: Dict[int, float] = {}     # uid -> absolute clock
        self._resume_prefix: Dict[int, List[int]] = {}   # restored progress
        self._pressure = 0                           # consecutive stragglers
        # instrumentation for benchmarks / regression tracking
        self.stats: Dict[str, Any] = {
            "decode_dispatches": 0, "prefills": 0, "tokens_out": 0,
            "wall_seconds": 0.0, "chunk_hist": chunk_hist(),
            "chunk_seconds_sum": 0.0, "rejected_dead": 0,
            "kv_pages_in_use": 0, "kv_pages_peak": 0, "prefix_hits": 0,
            "deadline_miss": 0, "shed_events": 0, "retries": 0,
            "error_completions": 0, "straggler_events": 0,
            "admission_blocked": 0, "queue_peak": 0,
        }
        if self.spec != "off":
            # per-iteration accepted-length histogram: bin i = iterations
            # that committed i+1 tokens (1 fed + i accepted drafts)
            self.stats["spec_draft_iters"] = 0
            self.stats["spec_emitted_tokens"] = 0
            self.stats["spec_accept_hist"] = [0] * (self.spec_k + 1)
        if self.paged:
            self._page_bytes = sum(
                leaf.nbytes // leaf.shape[pool_idx(leaf, nd)]
                for pl in jax.tree_util.tree_leaves(
                    self.cache,
                    is_leaf=lambda x: isinstance(x, PagedKVCache))
                if isinstance(pl, PagedKVCache)
                for leaf, nd in (
                    [(pl.pages.codes, 4), (pl.pages.scale, 3)]
                    if isinstance(pl.pages, QKVCache) else [(pl.pages, 4)]))
            self.stats["kv_hbm_bytes"] = 0
        else:
            # contiguous baseline: KV HBM is committed statically up front
            def _kv_bytes(ax, leaf):
                leaves = jax.tree_util.tree_leaves(leaf)
                flat_ax = jax.tree_util.tree_leaves(
                    ax, is_leaf=lambda x: isinstance(x, tuple))
                return sum(l.nbytes for l, a in zip(leaves, flat_ax)
                           if "cache_seq" in a)

            self.stats["kv_hbm_bytes"] = sum(
                jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                    _kv_bytes, axes, self.cache,
                    is_leaf=lambda x: isinstance(x, tuple))))
        self.stats["kv_hbm_bytes_peak"] = self.stats["kv_hbm_bytes"]

    # -- queue interface -----------------------------------------------------
    def _error(self, uid: int, tokens, reason: ErrorReason,
               msg: str) -> None:
        """Append a typed error completion (the only error path — keeps the
        exactly-one-Completion invariant auditable)."""
        self.completions.append(Completion(
            uid=uid, tokens=np.asarray(tokens, np.int32).reshape(-1),
            finish_reason="error", error=msg, reason=reason.value))
        self.stats["error_completions"] += 1
        self._deadline_at.pop(uid, None)

    def submit(self, request: Request) -> bool:
        """Enqueue a request; returns False (with an immediate ``queue_full``
        error completion) when the bounded frontend queue is full, and (with
        an immediate ``step_failure`` one) when a failed dispatch has left
        the engine dead."""
        if self._dead:
            self.stats["rejected_dead"] += 1
            self._error(request.uid, (), ErrorReason.STEP_FAILURE,
                        f"request {request.uid}: the engine is dead after a "
                        "failed dispatch")
            return False
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self._error(request.uid, (), ErrorReason.QUEUE_FULL,
                        f"request {request.uid}: queue full "
                        f"({len(self._queue)}/{self.max_queue} waiting)")
            return False
        dl = (request.deadline_ms if request.deadline_ms is not None
              else self.deadline_ms)
        if dl is not None:
            self._deadline_at[request.uid] = self._clock() + dl / 1e3
        self._queue.append(request)
        self.stats["queue_peak"] = max(self.stats["queue_peak"],
                                       len(self._queue))
        return True

    def _expired(self, uid: int) -> bool:
        at = self._deadline_at.get(uid)
        return at is not None and self._clock() >= at

    def _expire_deadlines(self) -> None:
        """Retire queued and in-flight requests whose deadline passed.

        Runs at the top of every step: a queued request never occupies a
        slot after expiry, and an active one returns its partial tokens with
        a ``deadline`` error completion (freeing the slot and its pages)."""
        if not self._deadline_at:
            return
        keep: Deque[Request] = deque()
        for req in self._queue:
            if self._expired(req.uid):
                self.stats["deadline_miss"] += 1
                self._error(req.uid, (), ErrorReason.DEADLINE,
                            f"request {req.uid}: deadline expired while "
                            "queued")
            else:
                keep.append(req)
        self._queue = keep
        for slot in list(self._active):
            if self._expired(self._active[slot].request.uid):
                self.stats["deadline_miss"] += 1
                self._retire(slot, "error", reason=ErrorReason.DEADLINE,
                             error="deadline expired during decode")

    def _prefill_batch(
            self, req: Request) -> Tuple[Dict[str, Any], int, np.ndarray]:
        """Build the bucketed batch-1 prefill batch; returns (batch, n, t)
        with ``n`` the true prompt length inside the bucket (prefix + tokens)
        and ``t`` the flat int32 prompt (reused by the page planner — no
        second host copy of the request tokens).

        Over-long (or empty) prompts raise :class:`PromptTooLongError` /
        ``ValueError`` — the engine never silently truncates a prompt.
        """
        t = np.asarray(req.tokens, np.int32).reshape(-1)
        if len(t) > self._tok_len:
            raise PromptTooLongError(
                f"request {req.uid}: prompt has {len(t)} tokens, engine "
                f"bucket holds {self._tok_len} (submit shorter prompts or "
                "build the engine with a larger prompt_len)")
        n = self._prefix_len + len(t)
        if n < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        toks = np.zeros((1, self._tok_len), np.int32)
        if self.paged:
            toks[0, :len(t)] = t          # right-pad; decode overwrites pads
        else:
            toks[0, self._tok_len - len(t):] = t
        batch: Dict[str, Any] = {"tokens": jnp.asarray(toks)}
        if req.extras:
            batch.update({k: jnp.asarray(v) for k, v in req.extras.items()})
        for k, sd in self._batch_template.items():
            if k not in batch:
                raise ValueError(
                    f"request {req.uid}: family {self.model.cfg.family!r} "
                    f"needs extras[{k!r}] shaped {sd.shape}")
            if tuple(batch[k].shape) != sd.shape:
                raise ValueError(
                    f"request {req.uid}: input {k!r} has shape "
                    f"{tuple(batch[k].shape)}, engine bucket needs {sd.shape}")
        return batch, n, t

    def _plan_pages(self, slot: int, toks: np.ndarray, n: int,
                    cap: int) -> Tuple[np.ndarray, np.ndarray]:
        """Allocate this slot's logical pages; returns (dest_rows, table_row).

        Pages are claimed up front for every position the slot can touch —
        the prefill bucket plus ``cap`` decode steps plus within-chunk
        overrun — so decode never needs to grow the table. ``dest_rows`` is
        where the prefill insert scatters each small-cache page: the slot's
        own pool row, or the trash page (0) for pages resolved by prefix
        sharing (their content already exists) and for unallocated tails.
        """
        ps, NP, T = self.page_size, self.n_logical_pages, self.cache_pos_len
        dest = np.zeros(NP, np.int32)
        trow = np.zeros(NP, np.int32)
        claimed: List[int] = []
        try:
            for i in range(self._worst_pages(n, cap)):
                key = ((i, toks[:(i + 1) * ps].tobytes())
                       if self._share_ok and (i + 1) * ps <= n else None)
                phys = self._alloc.lookup(key) if key is not None else None
                if phys is None:
                    phys = self._alloc.alloc("prefill page planning")
                    if key is not None:
                        self._alloc.register(phys, key)
                    dest[i] = phys           # owned: prefill writes the page
                claimed.append(phys)
                trow[i] = phys
        except PagePoolExhaustedError:
            for phys in claimed:             # roll back shares and claims
                self._alloc.decref(phys)
            raise
        self._host_table[slot] = trow
        return dest, trow

    def _worst_pages(self, n: int, cap: int) -> int:
        """Worst-case physical pages a request can ever touch: the prefill
        bucket plus ``cap`` decode steps plus within-chunk overrun (the
        trailing prefill-pad positions stay on the trash page). Admission
        reserves this many; COW only converts shared pages to private ones,
        which the reservation already double-counts, so the sum of
        reservations bounds true page demand."""
        ps, NP, T = self.page_size, self.n_logical_pages, self.cache_pos_len
        # positions beyond maxp hold only prefill pad rows, which decode never
        # writes and always reads causally masked: their pages stay on trash.
        # chunk_span covers within-chunk overrun incl. speculative k-ahead
        # writes; anything past it lands on the trash page, affecting only
        # tokens beyond the cap (which retirement drops)
        maxp = n + cap - 1 + self.chunk_span  # one past the last writable pos
        return min(-(-min(maxp, T) // ps), NP)

    def _refresh_page_stats(self) -> None:
        used = self._alloc.used
        self.stats["kv_pages_in_use"] = used
        self.stats["kv_pages_peak"] = max(self.stats["kv_pages_peak"], used)
        self.stats["kv_hbm_bytes"] = used * self._page_bytes
        self.stats["kv_hbm_bytes_peak"] = max(
            self.stats["kv_hbm_bytes_peak"], self.stats["kv_hbm_bytes"])
        self.stats["prefix_hits"] = self._alloc.hits
        # routing signal for the fleet dispatcher's least-loaded tiebreak
        self.stats["kv_pages_reserved"] = self._reserved_total

    def _admit(self) -> None:
        while self._free and self._queue:
            req = self._queue[0]
            # build+validate the batch BEFORE claiming a slot: a malformed
            # request raises to the caller without leaking concurrency —
            # except over-long/empty/misshaped prompts, which retire with an
            # explicit error completion so queue draining survives bad inputs
            try:
                batch, n, t = self._prefill_batch(req)
            except PromptTooLongError as e:
                self._queue.popleft()
                self._error(req.uid, (), ErrorReason.PROMPT_TOO_LONG, str(e))
                continue
            except ValueError as e:
                self._queue.popleft()
                self._error(req.uid, (), ErrorReason.BAD_REQUEST, str(e))
                continue
            cap = need = 0
            if self.paged:
                cap = min(req.max_new_tokens, self.max_len - n)
                need = self._worst_pages(n, cap)
                capacity = self.n_phys_pages - 1
                if need > capacity:
                    self._queue.popleft()
                    self._error(
                        req.uid, (), ErrorReason.PAGE_POOL,
                        f"request {req.uid}: needs {need} KV pages, pool "
                        f"holds {capacity} (shrink the request or grow "
                        "page_pool_pages)")
                    continue
                if self._reserved_total + need > capacity:
                    # backpressure: hold the request until retirements free
                    # reservations — never admit into possible exhaustion
                    self.stats["admission_blocked"] += 1
                    break
            self._queue.popleft()
            with tracing.span("engine.admit", uid=req.uid, prompt_len=len(t)):
                self._start(req, batch, n, t, cap, need)

    def _start(self, req: Request, batch: Dict[str, Any], n: int,
               t: np.ndarray, cap: int, need: int) -> None:
        """Prefill an admitted request and insert it into a free slot
        (``cap`` and ``need``: its token cap and reserved pages, paged)."""
        slot = self._free.pop()
        with tracing.span("engine.prefill", uid=req.uid,
                          bucket=self.prompt_len):
            logits, small = self._prefill(self.params, batch)
        if self.paged:
            first = jnp.argmax(logits[:, n - 1]).reshape(1, 1) \
                .astype(jnp.int32)
            try:
                dest, trow = self._plan_pages(slot, t, n, cap)
            except PagePoolExhaustedError as e:
                # reachable only under external pressure (chaos steal): the
                # reservation invariant covers engine-driven demand
                self._free.append(slot)
                self._error(req.uid, (), ErrorReason.PAGE_POOL, str(e))
                return
            self._reserved[slot] = need
            self._reserved_total += need
            args = (self.cache, self._tok, small, first, jnp.int32(slot),
                    jnp.asarray(dest), jnp.asarray(trow), jnp.int32(n))
            self._active[slot] = _Slot(request=req, n=n, cap=cap)
        else:
            cap = min(req.max_new_tokens, self.max_len - self.prompt_len)
            first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            args = (self.cache, self._tok, small, first, jnp.int32(slot),
                    jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
                    jnp.int32(0))
            self._active[slot] = _Slot(request=req, n=n, cap=cap)
        if self.spec != "off":
            (self.cache, self._tok, self._hist,
             self._hist_len) = self._insert(
                 *args, self._hist, self._hist_len, batch["tokens"],
                 jnp.int32(len(t)))
        else:
            self.cache, self._tok = self._insert(*args)
        if self._hook is not None:
            # absolute logits positions: true prompt end (paged right-pad)
            # vs bucket end (contiguous left-pad)
            base = n if self.paged else self.prompt_len
            pos = self._chaos.plan_request(req.uid, base, cap)
            self._arm_np[slot] = -1 if pos is None else pos
        if self.paged:
            self._refresh_page_stats()
        self.stats["prefills"] += 1

    def _ensure_writable(self) -> None:
        """Copy-on-write pass before a decode chunk: any page the chunk may
        write that is shared (refs > 1) gets copied to a fresh pool row, and
        sole-owned pages still in the prefix registry are unregistered —
        the first divergent write never lands on another slot's prefix."""
        ps, T = self.page_size, self.cache_pos_len
        for slot, st in list(self._active.items()):
            # surviving slots always satisfy device pos = n + len(produced):
            # EOS-truncated and cap-clamped slots retire at chunk end, so the
            # host count is exact for every slot still decoding (speculative
            # rollback rewinds pos to the committed length the same way)
            pos0 = st.n + len(st.produced)
            pages = {(p % T) // ps
                     for p in range(pos0, pos0 + self.chunk_span)}
            try:
                for i in sorted(pages):
                    phys = int(self._host_table[slot, i])
                    if phys == 0:
                        continue              # unallocated tail -> trash sink
                    if self._alloc.refs[phys] > 1:
                        new = self._alloc.alloc("copy-on-write")
                        self.cache = self._cow(
                            self.cache, jnp.int32(slot), jnp.int32(i),
                            jnp.int32(phys), jnp.int32(new))
                        self._alloc.refs[phys] -= 1
                        self._host_table[slot, i] = new
                    elif phys in self._alloc.reg_key:
                        self._alloc.unregister(phys)
            except PagePoolExhaustedError as e:
                # reachable only under external page pressure (reservation
                # covers engine-driven COW): quarantine this slot — its
                # partial tokens return with a typed error, its freed pages
                # let the remaining slots keep draining
                self._retire(slot, "error", reason=ErrorReason.PAGE_POOL,
                             error=str(e))

    def step(self) -> bool:
        """Admit waiting requests, run one fused decode chunk, retire finished
        slots. Returns False when fully drained.

        EOS detection ran on device inside the fused chunk (the scan carries
        a per-slot ``done`` flag and a valid-token count), so retirement here
        is a per-slot slice — no host-side scan over the token buffer."""
        if self._dead:
            return False
        idx = self.stats["decode_dispatches"]        # chunk index
        if self._chaos is not None and self.paged:
            self._chaos.page_pressure(self._alloc, idx)
        self._expire_deadlines()
        self._admit()
        if not self._active:
            return bool(self._queue)
        if self.paged:
            self._ensure_writable()
            self._refresh_page_stats()
            if not self._active:                     # COW quarantine emptied
                return bool(self._queue)
        # the watchdog window opens before fault handling: injected slow
        # chunks and retry backoff are exactly the stalls a straggler
        # monitor must see
        self._straggler.step_start()
        # transient faults fire BEFORE the dispatch and retry with backoff;
        # the dispatch itself is never replayed (its donated operands are
        # consumed), so a real dispatch exception fails everything over
        attempt = 0
        while self._chaos is not None:
            try:
                self._chaos.on_chunk(idx)
                break
            except TransientStepError as e:
                attempt += 1
                self.stats["retries"] += 1
                if attempt > self.max_retries:
                    self._fail_all(f"transient failure persisted past "
                                   f"{self.max_retries} retries: {e}")
                    return False
                time.sleep(self.retry_backoff_s * attempt)
        t0 = time.perf_counter()
        eos = jnp.int32(-1 if self.eos_id is None else self.eos_id)
        spec_live = self._spec_live != "off"
        gen = self._gen_shed if self._gen_shed is not None else self._generate
        args = (self.params, self.cache, self._tok, self._key, eos)
        if spec_live:
            args += (self._hist, self._hist_len)
        if self._hook is not None:
            args += (jnp.asarray(self._arm_np),)
        try:
            with tracing.span("engine.chunk", dispatch=idx,
                              slots=len(self._active)):
                out = gen(*args)
        except Exception as e:  # noqa: BLE001 — donated operands consumed
            self._fail_all(f"chunk dispatch failed: {e!r}")
            return False
        if spec_live:
            (self.cache, self._tok, self._key, done, n_valid, toks,
             self._hist, self._hist_len, acc, failed) = out
        else:
            (self.cache, self._tok, self._key, done, n_valid, toks,
             failed) = out
        with tracing.span("engine.sync", dispatch=idx):
            toks_np = np.asarray(toks)          # ONE host sync per chunk
            done_np = np.asarray(done)
            n_np = np.asarray(n_valid)
            failed_np = np.asarray(failed)
        dt = time.perf_counter() - t0
        self.stats["chunk_hist"][bisect.bisect_right(CHUNK_HIST_EDGES,
                                                     dt)] += 1
        self.stats["chunk_seconds_sum"] += dt
        self.stats["decode_dispatches"] += 1
        # watchdog: chunk dispatches slower than threshold x the wall-time
        # EMA are straggler events; `shed_after` consecutive events shed one
        # load level (speculation -> off, then chunk halved)
        if self._straggler.step_end(idx) is not None:
            self.stats["straggler_events"] += 1
            self._pressure += 1
            if self._pressure >= self.shed_after:
                self._shed()
                self._pressure = 0
        else:
            self._pressure = 0
        if spec_live:
            # accepted-length stats over live iterations of active slots only
            # (free/retired slots ride the fused chunk and emit garbage rows)
            acc_np = np.asarray(acc)[sorted(self._active)]
            live = acc_np[acc_np >= 0]
            self.stats["spec_draft_iters"] += int(live.size)
            self.stats["spec_emitted_tokens"] += int(live.sum())
            for c, freq in zip(*np.unique(live, return_counts=True)):
                self.stats["spec_accept_hist"][int(c) - 1] += int(freq)
        for slot in list(self._active):
            st = self._active[slot]
            st.chunks += 1
            take = min(int(n_np[slot]), st.cap - len(st.produced))
            st.produced.extend(int(t) for t in toks_np[slot][:take])
            if bool(done_np[slot]) and take == int(n_np[slot]):
                self._retire(slot, "eos")
            elif len(st.produced) >= st.cap:
                self._retire(slot, "length")
            elif bool(failed_np[slot]):
                # finite guard tripped on device: quarantine exactly this
                # slot — n_valid stopped at the last token sampled from
                # finite logits, so `produced` is the clean prefix
                self._retire(slot, "error", reason=ErrorReason.NAN_LOGITS,
                             error=f"non-finite logits after "
                                   f"{len(st.produced)} tokens; slot "
                                   "quarantined")
        return bool(self._active or self._queue)

    def _retire(self, slot: int, finish: str, *,
                reason: Optional[ErrorReason] = None,
                error: Optional[str] = None) -> None:
        st = self._active.pop(slot)
        with tracing.span("engine.retire", uid=st.request.uid,
                          tokens=len(st.produced),
                          reason=str(reason or finish)):
            self._free.append(slot)
            self._arm_np[slot] = -1
            self._reserved_total -= self._reserved.pop(slot, 0)
            if self.paged:
                for phys in self._host_table[slot]:
                    if phys:
                        self._alloc.decref(int(phys))
                self._host_table[slot] = 0
                # device table -> trash page: the retired slot keeps riding
                # the fused decode until reused, and its stale writes must
                # not land in pages the allocator may hand to someone else
                # (skipped when the engine is dead — the cache buffers may
                # be gone)
                if not self._dead:
                    self.cache = self._clear_slot(self.cache, jnp.int32(slot))
                self._refresh_page_stats()
            self.stats["tokens_out"] += len(st.produced)
            uid = st.request.uid
            self._deadline_at.pop(uid, None)
            produced = st.produced
            pre = self._resume_prefix.pop(uid, None)
            if pre:
                # restored request: tokens produced before the preemption
                # were re-prefilled as prompt suffix; the completion carries
                # the full stream so restore is invisible to callers
                produced = pre + produced
            if finish == "error":
                self._error(uid, produced,
                            reason or ErrorReason.STEP_FAILURE,
                            error or "unknown failure")
            else:
                self.completions.append(Completion(
                    uid=uid, tokens=np.asarray(produced, np.int32),
                    finish_reason=finish))

    def _fail_all(self, msg: str) -> None:
        """Unrecoverable dispatch failure: every in-flight and queued request
        completes with a typed ``step_failure`` error (partial tokens for
        active slots) and the engine goes dead — the exactly-one-Completion
        invariant survives even a poisoned jit."""
        self._dead = True
        for slot in list(self._active):
            self._retire(slot, "error", reason=ErrorReason.STEP_FAILURE,
                         error=msg)
        while self._queue:
            req = self._queue.popleft()
            self._error(req.uid, (), ErrorReason.STEP_FAILURE, msg)

    def _shed(self) -> None:
        """Load shedding, one level per call: (1) speculation off, (2) chunk
        halved (repeatable down to 1 token/dispatch). Greedy token streams
        are byte-identical across levels — the degraded program resumes from
        the same per-slot cache/pos/tok state at the chunk boundary — so
        shedding trades only latency mechanics, never output."""
        if self._spec_live != "off":
            self._spec_live = "off"
        elif self._chunk_live > 1:
            self._chunk_live = max(self._chunk_live // 2, 1)
        else:
            return
        self.stats["shed_events"] += 1
        gen_fn = make_generate_step(
            self.model, self.plan, chunk=self._chunk_live,
            temperature=self._temperature, top_k=self._top_k,
            spec="off", spec_k=0, logits_hook=self._hook)
        self._gen_shed = jax.jit(
            gen_fn, donate_argnums=(1,),
            out_shardings=(self._cache_sh,) + (self._rep,) * 6)

    # -- checkpoint / restore ------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """JSON-serializable serving state for a ``RestartManifest``: every
        not-yet-completed request (queued, or mid-decode with the tokens
        produced so far) plus the completions already emitted. Device state
        is deliberately NOT captured — restore re-prefills — so checkpoints
        stay tiny and layout/mesh-agnostic."""
        def entry(req: Request, produced: List[int]) -> Dict[str, Any]:
            toks = np.asarray(req.tokens, np.int32).reshape(-1).tolist()
            pre = self._resume_prefix.get(req.uid)
            if pre:      # already-restored request: split back to original
                produced = list(pre) + produced
                toks = toks[:len(toks) - len(pre)]
            d = {"uid": req.uid, "tokens": toks,
                 "max_new_tokens": int(req.max_new_tokens) + (len(pre or ())),
                 "produced": [int(x) for x in produced]}
            if req.deadline_ms is not None:
                d["deadline_ms"] = float(req.deadline_ms)
            return d

        return {
            "seed": self.seed,
            "temperature": self._temperature,
            "queued": [entry(r, []) for r in self._queue],
            "active": [entry(self._active[s].request,
                             list(self._active[s].produced))
                       for s in sorted(self._active)],
            "completions": [
                {"uid": c.uid, "tokens": np.asarray(c.tokens).tolist(),
                 "finish_reason": c.finish_reason, "error": c.error,
                 "reason": c.reason}
                for c in self.completions],
        }

    def load_snapshot(self, snap: Dict[str, Any],
                      resume: Optional[bool] = None) -> None:
        """Restore a :meth:`snapshot`: completions replay verbatim; queued
        and in-flight requests are resubmitted. With ``resume`` (default:
        paged layout + greedy sampling) an in-flight request re-prefills
        ``prompt + produced`` and decodes only the remainder — sound in the
        paged layout because right-padded prefill positions are
        bucket-independent, so the committed tokens reproduce the exact
        decode-time positions (the engine's ``prompt_len`` must fit the
        grown prompts). The contiguous layout left-pads to the bucket
        (absolute positions shift with prompt length), so it regenerates
        from scratch instead — greedy completions are byte-identical to an
        uninterrupted run either way."""
        if resume is None:
            resume = self.paged and self._temperature <= 0
        for c in snap.get("completions", ()):
            self.completions.append(Completion(
                uid=c["uid"], tokens=np.asarray(c["tokens"], np.int32),
                finish_reason=c["finish_reason"], error=c.get("error"),
                reason=c.get("reason")))
        for d in list(snap.get("queued", ())) + list(snap.get("active", ())):
            produced = [int(x) for x in d.get("produced") or ()]
            prompt = [int(x) for x in d["tokens"]]
            if resume and produced:
                self._resume_prefix[d["uid"]] = produced
                req = Request(
                    uid=d["uid"],
                    tokens=np.asarray(prompt + produced, np.int32),
                    max_new_tokens=d["max_new_tokens"] - len(produced),
                    deadline_ms=d.get("deadline_ms"))
            else:
                req = Request(uid=d["uid"],
                              tokens=np.asarray(prompt, np.int32),
                              max_new_tokens=d["max_new_tokens"],
                              deadline_ms=d.get("deadline_ms"))
            self.submit(req)

    def run(self, requests: Optional[List[Request]] = None, *,
            stop: Optional[Callable[[], bool]] = None) -> List[Completion]:
        """Drain the queue (plus ``requests``); returns all completions.

        ``stop`` is polled between chunks (e.g. a PreemptionHandler's
        ``requested`` flag): when it fires, draining halts at the chunk
        boundary with in-flight state intact — call :meth:`snapshot` next.
        """
        for r in requests or ():
            self.submit(r)
        t0 = time.perf_counter()
        while self.step():
            if stop is not None and stop():
                break
        # stats are cumulative across run() calls (the engine is reusable)
        self.stats["wall_seconds"] += time.perf_counter() - t0
        self.stats["tokens_per_second"] = self.stats["tokens_out"] / max(
            self.stats["wall_seconds"], 1e-9)
        self.stats["dispatches_per_token"] = (
            self.stats["decode_dispatches"] / max(self.stats["tokens_out"], 1))
        self.stats["kv_bytes_per_token"] = (
            self.stats["kv_hbm_bytes_peak"] / max(self.stats["tokens_out"], 1))
        if self.spec != "off":
            # mean tokens committed per draft-verify iteration (1.0 = nothing
            # accepted, spec_k+1 = every draft + bonus accepted)
            self.stats["spec_accepted_len_per_draft"] = (
                self.stats["spec_emitted_tokens"]
                / max(self.stats["spec_draft_iters"], 1))
        return self.completions

    @property
    def chaos_events(self) -> List[Dict[str, Any]]:
        """Injection log of the attached chaos harness ([] when unarmed)."""
        return [] if self._chaos is None else list(self._chaos.events)

    def compile_cache_size(self) -> Optional[int]:
        """Compiled-program count of the fused generate step (1 after warmup
        means no recompilation). None when the JAX version has no probe."""
        probe = getattr(self._generate, "_cache_size", None)
        return probe() if callable(probe) else None
