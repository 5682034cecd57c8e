"""Sharded serving fleet: health-checked dispatch and snapshot failover.

The contract under test, one level above ``test_robust_serving``: the
*shard* is the failure domain. A fleet of N engine shards behind one
dispatcher guarantees exactly one Completion per submitted request —
through shard kills, stalls, and dropped heartbeats — with surviving
outputs byte-identical to an undisturbed single-engine drain, and the
typed ``shard_lost`` reason only when replay is impossible.
"""
import os

import jax
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_config
from repro.core.mimdram import plan_sharding, use_plan
from repro.distributed import fault_tolerance as ft
from repro.distributed.chaos import ShardChaosConfig, ShardChaosMonkey
from repro.distributed.dispatcher import Dispatcher
from repro.distributed.fault_tolerance import (HealthMonitor, RestartManifest,
                                               ShardState)
from repro.launch import mesh as mesh_lib
from repro.launch.engine import Request, ServeEngine
from repro.launch.fleet import ServeFleet
from repro.models import build_model, init_params

PS = 4
ARCH = "pimref-100m"


def _engine(slots=2, prompt_len=8, max_new=8, chunk=4, **kw):
    cfg = get_config(ARCH, smoke=True)
    mesh = mesh_lib.make_local_mesh(("data",))
    plan = plan_sharding(
        cfg, ShapeConfig("serve", prompt_len + max_new, slots, "decode"),
        mesh)
    model = build_model(cfg)
    with use_plan(plan):
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    return ServeEngine(model, params, plan, slots=slots,
                       prompt_len=prompt_len, max_new=max_new, chunk=chunk,
                       **kw)


def _requests(n, prompt_len=8, max_new=8, seed=0):
    """Mixed-length prompts (the ROADMAP's 'mixed queue'): short ones keep
    prompt + produced inside the bucket (paged failover resumes from partial
    tokens), long ones overflow it (failover regenerates) — both replay
    paths run in every chaos drain."""
    cfg = get_config(ARCH, smoke=True)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        ln = int(rng.integers(3, prompt_len + 1))
        reqs.append(Request(
            uid=i, tokens=rng.integers(1, cfg.vocab_size, ln).astype(np.int32),
            max_new_tokens=max_new))
    return reqs


def _fleet(shards=2, chaos=None, **fleet_kw):
    return ServeFleet(lambda sid: _engine(), shards=shards,
                      chaos=chaos, **fleet_kw)


def _assert_exactly_one_each(fleet, n):
    uids = sorted(c.uid for c in fleet.completions)
    assert uids == list(range(n)), uids


def _assert_identical(fleet, ref_by_uid):
    """Non-error fleet completions match the reference byte-for-byte."""
    checked = 0
    for c in fleet.completions:
        if c.finish_reason == "error":
            continue
        want = ref_by_uid[c.uid]
        assert list(np.asarray(c.tokens)) == list(np.asarray(want.tokens)), (
            f"uid={c.uid}: {np.asarray(c.tokens)} != "
            f"{np.asarray(want.tokens)}")
        checked += 1
    return checked


@pytest.fixture(scope="module")
def ref_paged():
    """Single-engine paged drain of the standard queue — the byte-identity
    oracle every fleet drain is compared against."""
    os.environ["REPRO_KV_PAGES"] = str(PS)
    try:
        eng = _engine()
        eng.run(_requests(6))
    finally:
        os.environ.pop("REPRO_KV_PAGES", None)
    return {c.uid: c for c in eng.completions}


# ---------------------------------------------------------------------------
# Control plane units (no engine builds)
# ---------------------------------------------------------------------------
def test_health_monitor_escalation_and_sticky_death():
    m = HealthMonitor(2, miss_suspect=2, miss_dead=4)
    assert m.state(0) is ShardState.LIVE and m.live_shards == [0, 1]
    assert m.miss(0, 0) is ShardState.LIVE          # one miss: still live
    assert m.miss(0, 1) is ShardState.SUSPECT       # threshold
    assert m.beat(0, 2) is ShardState.LIVE          # heartbeat revives
    assert m.recoveries == 1 and m.suspects == 1
    for step in range(4):
        m.miss(0, 3 + step)
    assert m.state(0) is ShardState.DEAD and m.deaths == 1
    assert m.beat(0, 9) is ShardState.DEAD          # zombies stay dead
    assert m.dead_shards == [0] and m.live_shards == [1]
    assert [e["kind"] for e in m.events] == ["suspect", "recover", "suspect",
                                             "dead"]
    assert m.mark_dead(0, 10, "again") is ShardState.DEAD
    assert m.deaths == 1                            # idempotent


def test_dispatcher_least_loaded_with_reservation_tiebreak():
    mon = HealthMonitor(3)
    d = Dispatcher(mon)
    assert d.route() == 0                           # all idle: lowest sid
    d.assign(10, 0)
    assert d.route() == 1
    d.assign(11, 1)
    d.note_reserved(2, 7)                           # loads equal below:
    d.assign(12, 2)
    d.note_reserved(0, 3)
    d.note_reserved(1, 5)
    assert d.route() == 0                           # fewest reserved pages
    assert d.route(exclude={0}) == 1
    mon.states[0] = ShardState.SUSPECT
    assert d.route() == 1                           # suspect: no new work
    mon.states[1] = mon.states[2] = ShardState.DEAD
    assert d.route() == 0                           # only suspect left
    mon.states[0] = ShardState.DEAD
    assert d.route() is None                        # fleet dead
    assert d.fail_shard(1) == [11] and d.outstanding == 2
    d.complete(10)
    assert d.outstanding == 1 and d.home(12) == 2


def test_shard_chaos_parse_seeding_and_fire_once():
    cfg = ShardChaosConfig.parse("kill=1@2, stall=0@4,drop=1@3x2", seed=5)
    assert cfg.kill_targets == {1: 2} and cfg.stall_targets == {0: 4}
    assert cfg.drop_targets == {1: (3, 2)} and cfg.armed and cfg.seed == 5
    with pytest.raises(ValueError, match="unknown shard fault"):
        ShardChaosConfig.parse("explode=1@2")
    assert not ShardChaosConfig().armed

    mk = ShardChaosMonkey(cfg, 2)
    assert mk.directive(1, 2)["kind"] == "kill"
    assert mk.directive(1, 2) is None               # fire-once
    assert mk.directive(0, 4)["steps"] == cfg.stall_steps
    assert mk.directive(1, 3)["beats"] == 2
    assert [e["kind"] for e in mk.events] == ["kill", "stall", "drop"]

    seeded = ShardChaosMonkey(ShardChaosConfig.parse("kills=1,seed=3"), 4)
    again = ShardChaosMonkey(ShardChaosConfig.parse("kills=1,seed=3"), 4)
    assert seeded._plan == again._plan and len(seeded._plan) == 1


def test_restart_manifest_save_is_atomic(tmp_path, monkeypatch):
    """A crash mid-save must leave the previous manifest intact and no tmp
    turd behind — regression for the pre-atomic torn-write window."""
    path = str(tmp_path / "manifest.json")
    man = RestartManifest(step=1, checkpoint_dir="", mesh_shape=[1],
                          mesh_axes=["data"], data_seed=0)
    man.save(path)
    assert RestartManifest.load(path).step == 1

    def torn_dump(obj, f, *a, **kw):
        f.write('{"step": 999, "torn":')            # partial bytes hit disk
        raise RuntimeError("killed mid-save")

    monkeypatch.setattr(ft.json, "dump", torn_dump)
    with pytest.raises(RuntimeError, match="killed mid-save"):
        RestartManifest(step=2, checkpoint_dir="", mesh_shape=[1],
                        mesh_axes=["data"], data_seed=0).save(path)
    monkeypatch.undo()
    assert RestartManifest.load(path).step == 1     # old manifest survives
    assert os.listdir(tmp_path) == ["manifest.json"]  # tmp cleaned up


# ---------------------------------------------------------------------------
# In-process fleet: identity, failover, health transitions
# ---------------------------------------------------------------------------
def test_two_shard_fleet_drains_byte_identical_to_one(monkeypatch, ref_paged):
    """The ROADMAP gate, in-process half: the same mixed queue drains to the
    same bytes through 2 shards, 1 shard, and a bare engine."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    for shards in (2, 1):
        fleet = _fleet(shards=shards)
        fleet.run(_requests(6))
        _assert_exactly_one_each(fleet, 6)
        assert _assert_identical(fleet, ref_paged) == 6
        assert fleet.stats["failovers"] == 0
        assert fleet.stats["error_completions"] == 0
        if shards == 2:   # both shards actually served
            per = fleet.per_shard_stats()
            assert all(r["tokens_out"] > 0 for r in per)
            assert sum(r["tokens_out"] for r in per) == \
                fleet.stats["tokens_out"]


@pytest.mark.parametrize("layout", ["contig", "paged"])
def test_shard_kill_mid_drain_fails_over_exactly_once(monkeypatch, tmp_path,
                                                      layout, ref_paged):
    """Chaos kill mid-drain: every request still completes exactly once,
    byte-identical to the undisturbed drain (paged shards resume from the
    checkpointed partial tokens; contiguous shards regenerate), and the
    per-shard RestartManifest checkpoints land on disk."""
    if layout == "paged":
        monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
        ref = ref_paged
    else:
        eng = _engine()
        eng.run(_requests(6))
        ref = {c.uid: c for c in eng.completions}
    # kill at step 1: the victim's slot-resident requests are mid-decode
    # with one checkpointed chunk, so failover replays partial progress
    fleet = _fleet(chaos=ShardChaosConfig.parse("kill=1@1"),
                   manifest_dir=str(tmp_path))
    fleet.run(_requests(6))
    _assert_exactly_one_each(fleet, 6)
    assert fleet.stats["failovers"] == 1
    assert fleet.stats["replays"] >= 1
    assert fleet.stats["shard_lost"] == 0           # survivor absorbed it all
    assert fleet.monitor.state(1) is ShardState.DEAD
    assert _assert_identical(fleet, ref) == 6       # no errors at all
    # the periodic checkpoints are atomic RestartManifests, one per shard
    man = RestartManifest.load(str(tmp_path / "shard0.json"))
    assert man.shape == "fleet-shard0" and man.serve is not None
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_stalled_shard_escalates_miss_suspect_dead_then_fails_over(
        monkeypatch, ref_paged):
    """A hung shard (no reply, not dead) walks the miss -> suspect -> dead
    escalation before failover — and its requests still drain identically
    because the stall did no work after the last checkpoint."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    fleet = _fleet(chaos=ShardChaosConfig.parse("stall=1@1"),
                   miss_suspect=2, miss_dead=3)
    fleet.run(_requests(6))
    _assert_exactly_one_each(fleet, 6)
    assert fleet.monitor.state(1) is ShardState.DEAD
    assert fleet.monitor.suspects == 1 and fleet.stats["failovers"] == 1
    kinds = [e["kind"] for e in fleet.monitor.events]
    assert kinds == ["suspect", "dead"]
    assert fleet.stats["heartbeat_misses"] >= 3
    assert _assert_identical(fleet, ref_paged) == 6


def test_dropped_heartbeats_suspect_then_recover_without_failover(
        monkeypatch, ref_paged):
    """Dropped heartbeats from a shard that keeps working: SUSPECT pauses
    new routing, the next beat revives it, and nothing fails over."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    fleet = _fleet(chaos=ShardChaosConfig.parse("drop=1@1x2"),
                   miss_suspect=2, miss_dead=6)
    fleet.run(_requests(6))
    _assert_exactly_one_each(fleet, 6)
    assert fleet.stats["failovers"] == 0
    assert fleet.monitor.suspects == 1 and fleet.monitor.recoveries == 1
    assert fleet.monitor.state(1) is ShardState.LIVE
    assert _assert_identical(fleet, ref_paged) == 6


def test_whole_fleet_dead_yields_typed_shard_lost(monkeypatch):
    """No survivor to replay on: outstanding requests complete with the
    typed ``shard_lost`` reason, partial tokens preserved from the last
    checkpoint — and late submissions are refused the same way. The
    exactly-one invariant survives total fleet loss."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    fleet = _fleet(shards=1, chaos=ShardChaosConfig.parse("kill=0@1"))
    fleet.run(_requests(3))
    _assert_exactly_one_each(fleet, 3)
    comps = {c.uid: c for c in fleet.completions}
    assert all(c.finish_reason == "error" and c.reason == "shard_lost"
               for c in comps.values())
    # the two slot-resident requests got one chunk (step 0) checkpointed
    assert sorted(len(c.tokens) for c in comps.values()) == [0, 4, 4]
    assert fleet.stats["shard_lost"] == 3
    fleet.submit(Request(uid=99, tokens=np.arange(1, 5, dtype=np.int32),
                         max_new_tokens=4))
    late = [c for c in fleet.completions if c.uid == 99]
    assert len(late) == 1 and late[0].reason == "shard_lost"


# ---------------------------------------------------------------------------
# Multiprocessing shards (the CPU multi-host gate)
# ---------------------------------------------------------------------------
def test_mp_backend_refused_on_tpu(monkeypatch):
    """A chip belongs to one process: on a TPU host the mp stand-in would
    have every worker contend for the same chips, so it refuses and names
    the in-process per-device fleet instead of hanging."""
    from repro.launch.serve import make_fleet
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="inproc"):
        make_fleet(ARCH, shards=2, backend="mp")


def test_inproc_shards_are_one_device_replicas():
    from repro.launch.serve import make_fleet
    fleet = make_fleet(ARCH, shards=2, slots=2, prompt_len=8, gen=4,
                       chunk=4)
    try:
        devs = jax.devices()
        for sid, sh in enumerate(fleet.shards):
            assert list(sh.eng.plan.mesh.devices.flat) == \
                [devs[sid % len(devs)]]
            for leaf in jax.tree_util.tree_leaves(sh.eng.params):
                assert leaf.devices() == {devs[sid % len(devs)]}
    finally:
        fleet.close()


def test_mp_two_shard_fleet_drains_byte_identical(ref_paged, monkeypatch):
    """The ROADMAP gate: a 2-shard multiprocessing fleet drains the mixed
    queue byte-identical to a single engine, with both workers serving."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    from repro.launch.serve import make_fleet
    fleet = make_fleet(ARCH, shards=2, backend="mp", slots=2, prompt_len=8,
                       gen=8, chunk=4, seed=0)
    try:
        fleet.run(_requests(6))
        _assert_exactly_one_each(fleet, 6)
        assert _assert_identical(fleet, ref_paged) == 6
        per = fleet.per_shard_stats()
        assert all(r["tokens_out"] > 0 for r in per)
    finally:
        fleet.close()


def test_mp_shard_kill_is_a_real_terminate_and_fails_over(ref_paged,
                                                          monkeypatch):
    """Chaos kill on the mp backend SIGKILLs the worker process; the fleet
    detects death through process liveness (not a cooperative flag), fails
    over, and still delivers every request exactly once, byte-identical."""
    monkeypatch.setenv("REPRO_KV_PAGES", str(PS))
    from repro.launch.serve import make_fleet
    fleet = make_fleet(ARCH, shards=2, backend="mp", slots=2, prompt_len=8,
                       gen=8, chunk=4, seed=0,
                       fleet_chaos=ShardChaosConfig.parse("kill=1@2"))
    try:
        fleet.run(_requests(6))
        _assert_exactly_one_each(fleet, 6)
        assert fleet.stats["failovers"] == 1
        assert fleet.monitor.state(1) is ShardState.DEAD
        assert not fleet.shards[1].proc.is_alive()
        assert _assert_identical(fleet, ref_paged) == 6
    finally:
        fleet.close()
