"""``kernel.flash_bwd_roofline`` on synthetic train-step traces, reduced by
``trace.reduce`` from op events as a TPU profile holds them."""
import pytest

from chipbench_tiny import PEAKS
from benchmarks.chip import spec, trace
from benchmarks.chip.record import RunRecord

MS = 1_000_000      # ns
KERNEL = 'custom_call_target="tpu_custom_call"'
WORK = {"attn_call_flops": 1e9, "attn_call_bytes": 1e6}


def summary(kernels, steps=2):
    """A trace of ``steps`` train steps of 10 ms, each running
    ``kernels``: (HLO name, ms) pairs, back to back, and a copy."""
    modules, ops = [], []
    for n in range(steps):
        t0 = n * 10 * MS
        modules.append((f"jit_train_step({n})", t0, t0 + 10 * MS))
        t = t0
        for name, ms in kernels:
            ops.append((f"%{name}.{n} = bf16[8] custom-call(%q), {KERNEL}",
                        t, t + int(ms * MS)))
            t += int(ms * MS)
        ops.append((f"%copy.{n} = bf16[8] copy(%a)", t, t + MS))
    return trace.reduce({"/device:TPU:0": {"modules": modules, "ops": ops}},
                        [], window=(0, steps * 10 * MS))


def run_of(summary):
    return RunRecord(kind="train", seconds=1.0, peaks=PEAKS, trace=summary,
                     work=dict(WORK))


def roofline(run):
    return spec.load_reader("kernel.flash_bwd_roofline")(run)


def test_fused_kernel_counts_its_calls_and_time_alone():
    """Three backward calls a step of 0.5 ms each beside the forward: 2.5x
    the forward's operations per call over the backward's time alone."""
    s = summary([("flash_fwd", 1.0), ("flash_bwd", 0.5)] * 3)
    got = roofline(run_of(s))
    assert got == pytest.approx(100 * 2.5 * 1e9 / 197e12 / 0.5e-3)
    # every Pallas op of the step, where no kernel is named
    assert trace.kernel_count(s, "train_step") == 12


def test_split_kernels_count_the_dkv_calls():
    """dq in a kernel of its own: both kernels' time, the dkv calls."""
    s = summary([("flash_bwd_dkv", 0.3), ("flash_bwd_dq", 0.2)] * 3)
    assert roofline(run_of(s)) == pytest.approx(
        100 * 2.5 * 1e9 / 197e12 / 0.5e-3)


def test_memory_bound_call_reads_its_bytes():
    """Where the bytes bound the call, twice the forward's bytes count."""
    r = run_of(summary([("flash_bwd", 0.5)]))
    r.work = {"attn_call_flops": 1.0, "attn_call_bytes": 1e8}
    assert roofline(r) == pytest.approx(100 * 2e8 / 819e9 / 0.5e-3)


@pytest.mark.parametrize("kernels", [
    [("flash_fwd", 1.0)],                    # the parent: a jnp backward
    [("flash_decode", 1.0)],
])
def test_silent_without_the_backward_kernel(kernels):
    assert roofline(run_of(summary(kernels))) is None


def test_silent_without_a_trace():
    assert roofline(RunRecord(kind="train", seconds=1.0, peaks=PEAKS,
                              work=dict(WORK))) is None


def test_forward_roofline_counts_the_forward_kernel_alone():
    """The forward's reader takes the time and the calls of
    ``pallas:flash_fwd`` alone, beside the backward kernel in the step."""
    s = summary([("flash_fwd", 1.0), ("flash_bwd", 0.5)] * 3)
    got = spec.load_reader("kernel.flash_fwd_roofline")(run_of(s))
    # 6 forward calls of 1 ms over two steps: 1e9 operations a call
    assert got == pytest.approx(100 * 1e9 / 197e12 / 1e-3)
    assert spec.load_reader("kernel.flash_fwd_roofline")(
        run_of(summary([("flash_bwd", 0.5)]))) is None
