"""Fault-tolerant serving: admission control, deadlines, chaos injection,
graceful degradation, and checkpoint/restore.

The contract under test: every submitted request ends in exactly one
Completion (success or a typed error ``reason``), faults quarantine only the
request they hit, and every fault-free completion is byte-identical to a
fault-free drain — across the contiguous, paged, quantized-paged, and
speculative configurations.
"""
import jax
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_config
from repro.core.mimdram import plan_sharding, use_plan
from repro.distributed.chaos import ChaosConfig, TransientStepError
from repro.launch import mesh as mesh_lib
from repro.launch.engine import (ErrorReason, PagePoolExhaustedError, Request,
                                 ServeEngine, _PageAllocator)
from repro.models import build_model, init_params

PS = 4          # page size for paged configurations
ARCH = "pimref-100m"


def _build(slots, prompt_len, max_len):
    cfg = get_config(ARCH, smoke=True)
    mesh = mesh_lib.make_local_mesh(("data",))
    plan = plan_sharding(cfg, ShapeConfig("serve", max_len, slots, "decode"),
                         mesh)
    model = build_model(cfg)
    with use_plan(plan):
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    return cfg, model, params, plan


def _engine(slots=2, prompt_len=8, max_new=8, chunk=4, **kw):
    cfg, model, params, plan = _build(slots, prompt_len, prompt_len + max_new)
    return cfg, ServeEngine(model, params, plan, slots=slots,
                            prompt_len=prompt_len, max_new=max_new,
                            chunk=chunk, **kw)


def _requests(cfg, n, prompt_len=8, max_new=8, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    tokens=rng.integers(1, cfg.vocab_size,
                                        prompt_len).astype(np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def _by_uid(eng):
    return {c.uid: c for c in eng.completions}


def _set_layout(monkeypatch, layout):
    if layout in ("paged", "paged_q8"):
        monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    if layout == "paged_q8":
        monkeypatch.setenv("REPRO_KV_QUANT", "int8")
    if layout == "spec":
        monkeypatch.setenv("REPRO_SPEC_DECODE", "ngram")


# ---------------------------------------------------------------------------
# Typed errors and chaos plumbing (no engine builds)
# ---------------------------------------------------------------------------
def test_page_allocator_typed_exhaustion():
    """The allocator raises a typed error carrying pool stats, never a bare
    IndexError from an empty free list."""
    alloc = _PageAllocator(3)                      # 2 usable (row 0 = trash)
    assert alloc.alloc() == 1 and alloc.alloc() == 2
    with pytest.raises(PagePoolExhaustedError) as ei:
        alloc.alloc("unit test")
    assert ei.value.pool_stats == {"n_phys": 3, "free": 0, "used": 2,
                                   "registered": 0}
    assert "unit test" in str(ei.value)
    alloc.decref(2)                                # freed pages allocate again
    assert alloc.alloc() == 2


def test_error_reason_enum_is_the_shared_vocabulary():
    assert {r.value for r in ErrorReason} == {
        "prompt_too_long", "bad_request", "queue_full", "deadline",
        "page_pool", "nan_logits", "step_failure", "shard_lost"}
    assert str(ErrorReason.NAN_LOGITS) == "nan_logits"


def test_chaos_config_parse_and_env(monkeypatch):
    cfg = ChaosConfig.parse("nan=1, slow=2,fail=1,pages=4,slow_ms=7", seed=9)
    assert (cfg.nan, cfg.slow, cfg.fail, cfg.pages) == (1, 2, 1, 4)
    assert cfg.slow_ms == 7.0 and cfg.seed == 9 and cfg.wants_nan
    with pytest.raises(ValueError, match="unknown chaos knob"):
        ChaosConfig.parse("bogus=1")
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    assert ChaosConfig.from_env() is None
    monkeypatch.setenv("REPRO_CHAOS", "fail=2")
    env = ChaosConfig.from_env(seed=5)
    assert env.fail == 2 and env.seed == 5 and not env.wants_nan


# ---------------------------------------------------------------------------
# Admission control and backpressure
# ---------------------------------------------------------------------------
def test_bounded_queue_rejects_with_queue_full():
    """Submissions past ``max_queue`` complete immediately with a typed
    ``queue_full`` error; accepted work still drains."""
    cfg, eng = _engine(slots=1, max_queue=1)
    reqs = _requests(cfg, 3, max_new=4)
    assert eng.submit(reqs[0]) is True             # waiting: 0 -> accepted
    assert eng.submit(reqs[1]) is False            # waiting: 1 == max_queue
    assert eng.submit(reqs[2]) is False
    eng.run()
    comps = _by_uid(eng)
    assert comps[0].finish_reason == "length" and len(comps[0].tokens) == 4
    for uid in (1, 2):
        assert comps[uid].finish_reason == "error"
        assert comps[uid].reason == "queue_full"
        assert len(comps[uid].tokens) == 0
    assert eng.stats["error_completions"] == 2
    assert sorted(comps) == [0, 1, 2]              # exactly one each


def test_paged_admission_reserves_worst_case_pages(monkeypatch):
    """A pool sized for one worst-case request serializes admissions
    (backpressure, never exhaustion) and stays byte-identical to a drain
    through a full-size pool."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    # worst_pages(n=8, cap=8) = ceil(min(8+8-1+4, 16)/4) = 4 = the whole pool
    cfg, eng = _engine(page_pool_pages=4)
    assert eng.paged and eng.n_phys_pages == 5
    reqs = _requests(cfg, 3)
    eng.run(reqs)
    comps = _by_uid(eng)
    assert all(c.finish_reason == "length" for c in comps.values())
    assert eng.stats["admission_blocked"] > 0      # slots outnumber the pool
    assert eng.stats["error_completions"] == 0     # reservation never busts
    assert eng.stats["kv_pages_in_use"] == 0
    _, ref = _engine()                             # default full-size pool
    ref.run(_requests(cfg, 3))
    for uid, c in _by_uid(ref).items():
        assert comps[uid].tokens.tolist() == c.tokens.tolist(), uid


def test_paged_oversized_request_fails_fast(monkeypatch):
    """A request whose worst-case page demand exceeds the whole pool errors
    immediately (typed ``page_pool``) instead of deadlocking admission."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    cfg, eng = _engine(page_pool_pages=3)          # capacity 3 < need 4
    eng.run(_requests(cfg, 1))
    (c,) = eng.completions
    assert c.finish_reason == "error" and c.reason == "page_pool"
    assert "pool holds 3" in c.error


def test_chaos_page_steal_hits_typed_exhaustion(monkeypatch):
    """External page pressure (chaos stealing the free list) surfaces as a
    typed ``page_pool`` error on the request that needed the pages — the
    rest of the queue drains normally."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    chaos = ChaosConfig(seed=0, pages=99, steal_after_chunk=1)
    cfg, eng = _engine(slots=1, chaos=chaos)
    eng.run(_requests(cfg, 2))
    comps = _by_uid(eng)
    assert comps[0].finish_reason == "length"      # admitted before the steal
    assert comps[1].finish_reason == "error"
    assert comps[1].reason == "page_pool"
    assert "exhausted" in comps[1].error
    assert any(e["kind"] == "pages" for e in eng.chaos_events)


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["contig", "paged"])
def test_deadline_retires_queued_and_active(layout, monkeypatch):
    """With an injected clock: an expired active request returns its partial
    tokens with a ``deadline`` error, an expired queued request returns
    empty, and deadline-free survivors are byte-identical to a fault-free
    drain."""
    _set_layout(monkeypatch, layout)
    clk = {"t": 100.0}
    cfg, eng = _engine(slots=1, clock=lambda: clk["t"])
    reqs = _requests(cfg, 3)
    reqs[0].deadline_ms = 1000.0
    reqs[1].deadline_ms = 1000.0
    for r in reqs:
        eng.submit(r)
    assert eng.step()                              # admit uid 0, one chunk
    assert len(eng._active) == 1
    clk["t"] += 10.0                               # both deadlines expire
    eng.run()
    comps = _by_uid(eng)
    assert comps[0].finish_reason == "error" and comps[0].reason == "deadline"
    assert 0 < len(comps[0].tokens) < 8            # partial: one chunk's worth
    assert comps[1].finish_reason == "error" and comps[1].reason == "deadline"
    assert len(comps[1].tokens) == 0               # expired while queued
    assert comps[2].finish_reason == "length"      # no deadline: unaffected
    assert eng.stats["deadline_miss"] == 2
    if eng.paged:
        assert eng.stats["kv_pages_in_use"] == 0   # expiry freed the pages
    _, ref = _engine(slots=1)
    ref.run([_requests(cfg, 3)[2]])
    assert comps[2].tokens.tolist() == _by_uid(ref)[2].tokens.tolist()


# ---------------------------------------------------------------------------
# NaN quarantine (on-device finite guard)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["contig", "paged", "paged_q8", "spec"])
def test_nan_quarantine_is_per_slot(layout, monkeypatch):
    """Poisoned logits quarantine exactly the slot they hit: the victim
    returns the clean ``g+1``-token prefix with a ``nan_logits`` error, and
    every co-resident request decodes byte-identically to a fault-free
    drain — in all four cache/drafter configurations."""
    _set_layout(monkeypatch, layout)
    g = 2                                          # poison generated token g
    chaos = ChaosConfig(seed=0, nan_targets={1: g})
    cfg, eng = _engine(chaos=chaos)
    eng.run(_requests(cfg, 3))
    comps = _by_uid(eng)
    _, ref = _engine()
    ref.run(_requests(cfg, 3))
    refs = _by_uid(ref)
    bad = comps[1]
    assert bad.finish_reason == "error" and bad.reason == "nan_logits"
    assert len(bad.tokens) == g + 1
    assert bad.tokens.tolist() == refs[1].tokens.tolist()[:g + 1]
    for uid in (0, 2):
        assert comps[uid].finish_reason == refs[uid].finish_reason
        assert comps[uid].tokens.tolist() == refs[uid].tokens.tolist(), uid
    assert eng.chaos_events == [
        {"kind": "nan", "uid": 1,
         "pos": (eng.prompt_len if not eng.paged else 8) + g}]


# ---------------------------------------------------------------------------
# Transient failures: retry, then fail over
# ---------------------------------------------------------------------------
def test_transient_failure_retries_to_identity():
    """An injected pre-dispatch failure retries with backoff and the drain
    completes byte-identical to a fault-free run."""
    chaos = ChaosConfig(seed=0, fail_chunks=[1])
    cfg, eng = _engine(chaos=chaos, retry_backoff_s=0.0)
    eng.run(_requests(cfg, 3))
    assert eng.stats["retries"] == 1
    assert eng.stats["error_completions"] == 0
    _, ref = _engine()
    ref.run(_requests(cfg, 3))
    refs = _by_uid(ref)
    for uid, c in _by_uid(eng).items():
        assert c.tokens.tolist() == refs[uid].tokens.tolist(), uid


def test_persistent_failure_fails_over_every_request():
    """When the retry budget is exhausted, every in-flight and queued
    request gets a typed ``step_failure`` completion and the engine goes
    dead — never a hang, never a lost request."""
    cfg, eng = _engine(slots=1, chaos=ChaosConfig(),
                       max_retries=1, retry_backoff_s=0.0)

    def always_fail(idx):
        raise TransientStepError(f"persistent fault at chunk {idx}")

    eng._chaos.on_chunk = always_fail
    eng.run(_requests(cfg, 3))
    comps = _by_uid(eng)
    assert sorted(comps) == [0, 1, 2]
    for c in comps.values():
        assert c.finish_reason == "error" and c.reason == "step_failure"
    assert eng.stats["retries"] == 2               # 1 retry + the final trip
    assert eng.step() is False                     # dead engine stays dead


def test_submit_to_a_dead_engine_completes_at_once():
    """A request submitted after a failed dispatch killed the engine gets an
    immediate typed ``step_failure`` completion and is never queued, so
    every request still ends in exactly one Completion."""
    cfg, eng = _engine(slots=1, chaos=ChaosConfig(), max_retries=0,
                       retry_backoff_s=0.0)

    def always_fail(idx):
        raise TransientStepError(f"persistent fault at chunk {idx}")

    eng._chaos.on_chunk = always_fail
    eng.run(_requests(cfg, 1))
    assert eng.stats["rejected_dead"] == 0
    late = [Request(uid=r.uid + 10, tokens=r.tokens, max_new_tokens=4)
            for r in _requests(cfg, 2, seed=1)]
    assert [eng.submit(r) for r in late] == [False, False]
    assert not eng._queue
    assert eng.stats["rejected_dead"] == 2
    eng.run()                                      # drains at once
    comps = _by_uid(eng)
    assert sorted(comps) == [0, 10, 11] and len(eng.completions) == 3
    for uid in (10, 11):
        assert comps[uid].reason == "step_failure"
        assert comps[uid].tokens.size == 0


# ---------------------------------------------------------------------------
# Watchdog + load shedding
# ---------------------------------------------------------------------------
class _FlagOn:
    """Deterministic StragglerMonitor stand-in: flags exact chunk indices
    (wall-clock EMAs are compile-time-noisy in CI)."""

    def __init__(self, steps):
        self.steps, self.flagged = set(steps), []

    def step_start(self):
        pass

    def step_end(self, idx):
        if idx in self.steps:
            self.flagged.append({"step": idx, "seconds": 1.0, "ema": 0.1})
            return self.flagged[-1]
        return None


def test_straggler_watchdog_sheds_load_byte_identically():
    """Sustained straggler flags on the chunk dispatch shed load (here:
    chunk halved, twice); the token streams are byte-identical to an unshed
    drain — shedding trades latency mechanics, never output."""
    cfg, eng = _engine(max_new=16, shed_after=1)
    eng._straggler = _FlagOn({2, 3})
    eng.run(_requests(cfg, 4, max_new=16))
    assert eng.stats["straggler_events"] == 2
    assert eng.stats["shed_events"] == 2
    assert eng._chunk_live < eng.chunk             # degraded program live
    _, ref = _engine(max_new=16)
    ref.run(_requests(cfg, 4, max_new=16))
    refs = _by_uid(ref)
    for uid, c in _by_uid(eng).items():
        assert c.finish_reason == refs[uid].finish_reason
        assert c.tokens.tolist() == refs[uid].tokens.tolist(), uid


# ---------------------------------------------------------------------------
# Checkpoint / restore
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["contig", "paged"])
def test_snapshot_restore_drains_byte_identically(layout, monkeypatch):
    """Preempt mid-drain, snapshot, restore into a fresh engine, drain: the
    union of completions is byte-identical to an uninterrupted run. Paged
    restore resumes from prompt+produced; contiguous regenerates."""
    _set_layout(monkeypatch, layout)
    cfg, eng = _engine()
    for r in _requests(cfg, 4):
        eng.submit(r)
    eng.run(stop=lambda: eng.stats["decode_dispatches"] >= 1)
    snap = eng.snapshot()
    assert snap["active"]                          # preempted mid-decode
    assert any(d["produced"] for d in snap["active"])

    # paged resume re-prefills prompt + produced: the restored engine's
    # bucket must fit the grown prompts (the CLI's restore path does the
    # same arithmetic; page positions are true, so a bigger bucket cannot
    # change surviving tokens)
    need = max(len(d["tokens"]) + len(d["produced"])
               for d in snap["queued"] + snap["active"])
    _, eng2 = _engine(prompt_len=max(8, need) if layout == "paged" else 8)
    eng2.load_snapshot(snap)
    snap2 = eng2.snapshot()
    if eng2.paged:
        # double-snapshot: a snapshot taken before resuming round-trips to
        # the original prompts/progress (resume prefixes split back out)
        entries = lambda s: sorted(
            (d["uid"], tuple(d["tokens"]), tuple(d["produced"]),
             d["max_new_tokens"])
            for d in s["queued"] + s["active"])
        assert entries(snap2) == entries(snap)
    else:
        # contiguous restore regenerates: original prompts and caps survive,
        # mid-flight progress is intentionally discarded
        assert sorted(
            (d["uid"], tuple(d["tokens"]), d["max_new_tokens"])
            for d in snap2["queued"] + snap2["active"]) == sorted(
            (d["uid"], tuple(d["tokens"]), d["max_new_tokens"])
            for d in snap["queued"] + snap["active"])
    eng2.run()
    comps = _by_uid(eng2)

    _, ref = _engine()
    ref.run(_requests(cfg, 4))
    refs = _by_uid(ref)
    assert sorted(comps) == sorted(refs)
    for uid, c in refs.items():
        assert comps[uid].finish_reason == c.finish_reason
        assert comps[uid].tokens.tolist() == c.tokens.tolist(), uid


# ---------------------------------------------------------------------------
# The invariant under everything at once
# ---------------------------------------------------------------------------
def test_exactly_one_completion_under_mixed_chaos(monkeypatch):
    """Seeded NaN + slow + transient-failure + page-steal chaos on the
    quantized paged engine: the drain terminates and every submitted uid
    ends in exactly one completion, each with a typed reason when errored."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    monkeypatch.setenv("REPRO_KV_QUANT", "int8")
    chaos = ChaosConfig(seed=11, nan=2, slow=1, fail=1, pages=2, slow_ms=1.0,
                        steal_after_chunk=2)
    cfg, eng = _engine(chaos=chaos, retry_backoff_s=0.0)
    eng.run(_requests(cfg, 6))
    uids = sorted(c.uid for c in eng.completions)
    assert uids == list(range(6))                  # exactly one each
    valid = {r.value for r in ErrorReason}
    for c in eng.completions:
        if c.finish_reason == "error":
            assert c.reason in valid and c.error
        else:
            assert c.reason is None
    assert eng.stats["error_completions"] == sum(
        c.finish_reason == "error" for c in eng.completions)
