"""Device time by program scope and idle gaps by host span
(``benchmarks/chip/scopes.py``), on a small synthetic train-step trace and
the name stacks of its program's optimized HLO, against hand-computed
values; and the existing reduction (``trace.py``) unchanged beside it."""
import copy

import pytest

from chipbench_tiny import PEAKS
from benchmarks.chip import scopes, spec, trace
from benchmarks.chip.record import RunRecord

MS = 1_000_000      # ns
STEP = "jit(train_step)"


def step_ops(t0):
    """One train step of 10 ms from ``t0``: (op, stack) pairs."""
    def op(text, s, e):
        return (text, t0 + s * MS // 2, t0 + e * MS // 2)
    return [
        (op("%while.1 = (s32[]) while(%t)", 2, 14),
         f"{STEP}/jvp(layers)/while"),
        (op("%fusion.1 = bf16[8] fusion(%a)", 2, 6),
         f"{STEP}/jvp(layers)/while/body/closed_call/attn/dot_general"),
        (op('%flash_fwd.2 = bf16[8] custom-call(%q), '
            'custom_call_target="tpu_custom_call"', 6, 7),
         f"{STEP}/jvp(layers)/while/body/closed_call/attn/flash_fwd"),
        (op("%bitcast_dynamic-update-slice_fusion.3 = f32[8] fusion(%b)",
            7, 12),
         f"{STEP}/transpose(jvp(layers))/while/body/transpose(jvp(attn))/"
         "attn_bwd/dynamic_update_slice;" + f"{STEP}/other"),
        (op("%dynamic-update-slice.4 = f32[8] dynamic-update-slice(%c)",
            12, 14),
         f"{STEP}/jvp(layers)/while/body/dynamic_update_slice"),
        (op("%fusion.5 = f32[8] fusion(%d)", 14, 16),
         f"{STEP}/transpose(jvp(head_loss))/dot_general"),
        (op("%fusion.6 = f32[8] fusion(%e)", 16, 18),
         f"{STEP}/optimizer/mul"),
        (op("%select.7 = f32[8] select(%f)", 18, 19),
         f"{STEP}/guard/select_n"),
        (op("%copy.8 = f32[8] copy(%g)", 19, 20), ""),
    ]


def synthetic():
    """Two train steps on one chip, 0-10 ms and 12-22 ms, in a window of
    30 ms, as ``trace.load_xplane`` reads them; each operation's name stack
    as the program's optimized HLO holds it; the host's bench.* and
    repro.* annotations."""
    pairs = step_ops(0) + step_ops(12 * MS)
    modules = [("jit_train_step(3)", 0, 10 * MS),
               ("jit_train_step(3)", 12 * MS, 22 * MS)]
    devices = {"/device:TPU:0": {"modules": modules,
                                 "ops": [o for o, _ in pairs]}}
    names = {"jit_train_step": {scopes.instruction(o[0]): st
                                for o, st in pairs if st}}
    bench = [("bench.window", 0, 30 * MS), ("bench.step", 0, 11 * MS),
             ("bench.step", 11 * MS, 30 * MS)]
    program = [("repro.engine.sync", 10 * MS + MS // 4, 12 * MS)]
    return devices, names, bench, program


def test_stacks_from_the_optimized_hlo(tmp_path):
    """The name stacks come from each program's optimized HLO text, by
    operation name: the trace events of TPU operations hold none."""
    _, names, _, _ = synthetic()
    lines = ["HloModule jit_train_step, is_scheduled=true", "",
             "%fused_computation.1 (p: f32[8]) -> f32[8] {"]
    lines += [f'  %{n} = f32[8] fusion(%x), kind=kLoop, '
              f'metadata={{op_name="{st}" stack_frame_id=3}}'
              for n, st in names["jit_train_step"].items()]
    lines += ["  ROOT %copy.8 = f32[8] copy(%g)", "}"]   # no metadata
    (tmp_path / "module_0007.jit_train_step.tpu_after_optimizations.txt"
     ).write_text("\n".join(lines) + "\n")
    (tmp_path / "module_0007.jit_train_step.before_optimizations.txt"
     ).write_text("HloModule jit_train_step\n  %x.1 = f32[8] copy(%y), "
                  'metadata={op_name="jit(train_step)/attn/z"}\n')
    assert scopes.hlo_names(str(tmp_path)) == names
    assert scopes.instruction("%copy.1 = f32[8] copy(%a)") == "copy.1"
    assert scopes.instruction("fusion.2") == "fusion.2"


def test_program_spans_from_a_recorded_profile(tmp_path):
    import jax

    from repro.launch import tracing
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        with tracing.span("engine.chunk", dispatch=3, slots=2):
            pass
    jax.profiler.stop_trace()
    spans = scopes.program_spans(trace.find_xplane(str(tmp_path)))
    assert [n for n, _, _ in spans] == ["repro.engine.chunk"]
    assert all(e >= s for _, s, e in spans)


def test_scope_of_a_name_stack():
    assert scopes.scope_of(f"{STEP}/jvp(layers)/while/body/closed_call/"
                           "attn/dot_general") == ("attn", "fwd")
    assert scopes.scope_of(f"{STEP}/transpose(jvp(layers))/while/body/"
                           "transpose(jvp(attn))/attn_bwd/mul") == (
        "attn_bwd", "bwd")
    assert scopes.scope_of(f"{STEP}/transpose(jvp(embed))/scatter-add") == (
        "embed", "bwd")
    assert scopes.scope_of(f"{STEP}/transpose(jvp(layers))/while/body/"
                           "closed_call/checkpoint/rematted_computation/mlp/"
                           "dot_general") == ("mlp", "remat")
    assert scopes.scope_of(f"{STEP}/add_any") == ("unscoped", "fwd")
    assert scopes.scope_of("") == ("unscoped", "fwd")


def test_device_scopes_by_hand():
    devices, names, bench, program = synthetic()
    s = scopes.reduce(devices, bench + program, names)
    got = s["scope_in_module_s"]["jit_train_step"]
    # per step: attn 2 + 0.5 ms fwd, attn_bwd 2.5 ms bwd, layers 1 ms (the
    # while op's own time is 0: its body covers it), head_loss 1 ms bwd,
    # optimizer 1 ms, guard 0.5 ms, unscoped 0.5 ms; two steps
    assert got["attn"] == pytest.approx({"fwd": 5e-3})
    assert got["attn_bwd"] == pytest.approx({"bwd": 5e-3})
    assert got["layers"] == pytest.approx({"fwd": 2e-3})
    assert got["head_loss"] == pytest.approx({"bwd": 2e-3})
    assert got["optimizer"] == pytest.approx({"fwd": 2e-3})
    assert got["guard"] == pytest.approx({"fwd": 1e-3})
    assert got["unscoped"] == pytest.approx({"fwd": 1e-3})
    assert s["module_n"] == {"jit_train_step": 2.0}
    assert (s["named_ops"], s["unnamed_ops"]) == (16, 2)
    ops = s["op_in_scope_s"]["jit_train_step"]
    assert ops["attn_bwd/bwd"] == pytest.approx(
        {"bitcast_dynamic-update-slice_fusion": 5e-3})
    assert ops["attn/fwd"] == pytest.approx({"fusion": 4e-3,
                                             "pallas:flash_fwd": 1e-3})
    rows = s["device_scopes"]
    assert rows[0][:3] in (["jit_train_step", "attn", "fwd"],
                           ["jit_train_step", "attn_bwd", "bwd"])
    assert sum(r[3] for r in rows) == pytest.approx(18e-3)  # all self time
    assert scopes.scoped_share(s, "train_step") == pytest.approx(17 / 18)
    # idle: 0..1 ms (bench.step), 10..13 ms (its middle under
    # repro.engine.sync, the innermost), 22..30 ms (bench.step)
    gaps = {round(g * 1e3, 3): what for what, g in s["idle_gaps_by_span"]}
    assert gaps == {1.0: "bench.step", 3.0: "repro.engine.sync",
                    8.0: "bench.step"}


def test_train_step_metrics_by_hand():
    devices, names, bench, program = synthetic()
    s = scopes.reduce(devices, bench + program, names)
    assert scopes.train_step_metrics(s) == pytest.approx({
        "train.attn_bwd_ms_per_step": 2.5,
        "train.head_loss_ms_per_step": 1.0,
        "train.optimizer_ms_per_step": 1.5})
    # their sum stays inside the step's device time (9 ms busy of 10)
    assert sum(scopes.train_step_metrics(s).values()) < 9.0


def test_a_program_without_scopes_reads_nothing():
    devices, _, bench, _ = synthetic()
    s = scopes.reduce(devices, bench, {})
    assert set(s["scope_in_module_s"]["jit_train_step"]) == {"unscoped"}
    assert scopes.scoped_share(s, "train_step") == 0.0
    assert scopes.train_step_metrics(s) == {}
    assert scopes.train_step_metrics(None) == {}
    assert scopes.reduce({"/device:TPU:0": {"modules": [], "ops": []}},
                         bench, {}) is None


def test_the_existing_reduction_is_unchanged():
    """trace.reduce, and the readers of the accepted metrics, give the
    values hand-computed for the same trace: the scope reduction reads
    beside them and changes none (the harness passes trace.reduce the
    bench.* annotations alone, as trace.load_xplane keeps them)."""
    devices, names, bench, program = synthetic()
    before = copy.deepcopy(devices)
    a = trace.reduce(devices, bench)
    scopes.reduce(devices, bench + program, names)
    assert devices == before
    assert trace.reduce(devices, bench) == a
    assert a["window_s"] == pytest.approx(30e-3)
    assert a["busy_s"] == pytest.approx(18e-3)
    assert trace.module_count(a, "train_step") == 2
    assert trace.kernel_time(a, "train_step") == pytest.approx(1e-3)
    assert dict(a["device_ops"])["while"] == pytest.approx(0.0)
    assert [w for w, _ in a["idle_gaps"]] == ["bench.step"] * 3
    assert [g for _, g in a["idle_gaps"]] == pytest.approx([8e-3, 3e-3,
                                                             1e-3])
    run = RunRecord(kind="train", seconds=1.0, peaks=PEAKS, trace=a,
                    tokens_per_step=1024, traced_until=0.03,
                    traced_work={"steps": 2, "model_flops_per_token": 1e9},
                    work={"attn_call_flops": 1e9, "attn_call_bytes": 1e6})
    assert spec.load_reader("kernel.flash_fwd_roofline")(run) == (
        pytest.approx(100 * 1e9 / 197e12 / 0.5e-3))
    assert spec.load_reader("train.mfu")(run) == pytest.approx(
        100 * 2 * 1024 / 0.03 * 1e9 / 197e12)
