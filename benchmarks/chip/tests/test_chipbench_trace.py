"""The reduction from a trace to metrics, on a small synthetic trace, and the
operation and byte counts against hand-computed values."""
import pytest

from chipbench_tiny import PEAKS
from benchmarks.chip import roofline, spec, trace
from benchmarks.chip.record import RunRecord

MS = 1_000_000      # ns


KERNEL = ('%closed_call.11 = bf16[8,32,1,64] custom-call(s32[8,1,1] %c), '
          'custom_call_target="tpu_custom_call"')


def synthetic():
    """Two generate executions and one prefill on one chip; each holds a
    Pallas kernel named as the TPU trace names every kernel, the first two
    inside a scan's while op."""
    modules = [("jit_generate_step(41)", 0, 10 * MS),
               ("jit_prefill_step(7)", 12 * MS, 15 * MS),
               ("jit_generate_step(41)", 16 * MS, 26 * MS)]
    ops = [("%while.34 = (s32[]) while(%tuple.1)", 1 * MS, 9 * MS),
           (KERNEL, 1 * MS, 4 * MS),
           ("%fusion.3 = bf16[8] fusion(%a)", 4 * MS, 9 * MS),
           (KERNEL.replace("11", "12"), 12 * MS, 14 * MS),
           (KERNEL, 17 * MS, 20 * MS),
           ("%copy.160 = bf16[24] copy(%g)", 20 * MS, 25 * MS)]
    host = [("bench.window", 0, 30 * MS), ("bench.step", 0, 11 * MS),
            ("bench.wait", 11 * MS, 12 * MS), ("bench.step", 15 * MS,
                                               30 * MS)]
    return {"/device:TPU:0": {"modules": modules, "ops": ops}}, host


def test_merge():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_pallas_ops_are_attributed_to_their_module():
    devices, host = synthetic()
    att = trace.attribute(devices["/device:TPU:0"]["ops"],
                          devices["/device:TPU:0"]["modules"])
    mods = [m for _, m, _, _ in att]
    assert mods == ["jit_generate_step"] * 3 + ["jit_prefill_step"] + [
        "jit_generate_step"] * 2
    assert trace.op_name(KERNEL) == "pallas:closed_call"
    assert trace.op_name("%copy.160 = bf16[24] copy(%g)") == "copy"
    s = trace.reduce(devices, host)
    assert trace.kernel_time(s, "generate_step") == pytest.approx(6e-3)
    assert trace.kernel_time(s, "prefill_step") == pytest.approx(2e-3)
    assert trace.kernel_count(s, "generate_step") == 2
    assert trace.module_count(s, "generate_step") == 2
    assert trace.module_time(s, "prefill_step") == pytest.approx(3e-3)


def test_busy_idle_and_gaps_over_the_window():
    devices, host = synthetic()
    s = trace.reduce(devices, host)
    assert s["window_s"] == pytest.approx(30e-3)
    # busy: [1,9] + [12,14] + [17,25] = 18 ms
    assert s["busy_s"] == pytest.approx(18e-3)
    gaps = dict((round(g * 1e3), what) for what, g in s["idle_gaps"])
    assert gaps[5] == "bench.step"            # 25..30
    assert gaps[3] == "bench.wait" or gaps[3] == "bench.step"
    ops = dict(s["device_ops"])
    assert ops["pallas:closed_call"] == pytest.approx(8e-3)
    assert ops["while"] == pytest.approx(0.0)     # all of it is its body's
    assert ops["fusion"] == pytest.approx(5e-3)
    assert trace.reduce({"/device:TPU:0": {"modules": [], "ops": []}},
                        host) is None


def test_decode_counts_by_hand():
    # stablelm-1.6b: 32 heads of 64, one row over 600 valid positions
    f, b = roofline.decode_attention(600, 32, 32, 64)
    assert f == 4 * 32 * 64 * 600                      # q.k and p.v
    assert b == (2 * 32 * 600 + 2 * 32) * 64 * 2       # k, v; q, out
    share, bound = roofline.share(f, b, 1e-3, PEAKS)
    assert bound == "memory"
    assert share == pytest.approx(100 * b / 819e9 / 1e-3)


def test_causal_prefill_counts_by_hand():
    # pimref-100m: 12 heads of 64, a 1024-token causal sequence
    f, b = roofline.causal_attention(1024, 12, 12, 64)
    assert f == 4 * 12 * 64 * (1024 * 1025 / 2)        # the causal half
    assert b == 4 * 12 * 1024 * 64 * 2 + 4 * 12 * 1024
    share, bound = roofline.share(f, b, 1e-3, PEAKS)
    assert bound == "compute"
    assert share == pytest.approx(100 * f / 197e12 / 1e-3)


def test_matmul_params_by_hand():
    m = {"d_model": 768, "d_ff": 3072, "vocab_size": 32768, "num_layers": 12,
         "head_dim": 64, "num_heads": 12, "num_kv_heads": 12}
    per_layer = 768 * 64 * (2 * 12 + 2 * 12) + 3 * 768 * 3072
    dense = spec.load_family("dense")
    assert dense.matmul_params(m) == 12 * per_layer + 768 * 32768


def test_peaks_refuse_an_unknown_device():
    assert roofline.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks_for("TPU v4")


def test_roofline_reader_from_a_trace():
    devices, host = synthetic()
    run = RunRecord(kind="serve", seconds=1.0, window_s=0.03, peaks=PEAKS,
                    trace=trace.reduce(devices, host),
                    work={"chunk": 4},
                    traced_work={"decode_tokens": 10, "decode_attn_flops": 1e9,
                                 "decode_attn_bytes": 2e6})
    got = spec.load_reader("kernel.flash_decode_roofline")(run)
    assert got == pytest.approx(100 * max(1e9 / 197e12, 2e6 / 819e9) / 6e-3)
    assert spec.load_reader("decode.step_ms")(run) == pytest.approx(
        20.0 / (2 * 4))
