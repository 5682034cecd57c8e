"""Tails, rates and the TPOT rule over a whole window, from a made-up run."""
import math

import pytest

from chipbench_tiny import PEAKS
from benchmarks.chip import spec
from benchmarks.chip.record import (RequestRecord, RunRecord, percentile,
                                    tpot_s, ttft_s)


def req(uid, due, first, last, n, chunks, failed=False):
    return RequestRecord(uid=uid, due=due, max_new=n, submitted=due,
                         admitted=due + 0.1, first=first, last=last,
                         n_tokens=n, n_chunks=chunks, done=True,
                         failed=failed)


def chat_run():
    reqs = [req(i, float(i), i + 0.5 + 0.01 * i, i + 2.5, 21, 3)
            for i in range(20)]
    reqs.append(req(20, 19.5, 20.0, 20.0, 8, 1))       # one chunk: no TPOT
    reqs.append(req(21, 30.0, 31.0, 33.0, 20, 3))      # due after the close
    return RunRecord(kind="serve", seconds=20.0, window_s=20.0,
                     requests=reqs, peaks=PEAKS)


def test_nearest_rank_percentile_and_missing_values():
    assert percentile(range(1, 101), 90) == 90
    assert percentile([1, 2, 3], 90) == 3
    assert percentile([1.0] * 9 + [math.inf], 90) == 1.0
    assert percentile([1.0] * 8 + [math.inf] * 2, 90) == math.inf


def test_tpot_rule():
    r = req(0, 0.0, 1.0, 3.0, 21, 3)
    assert tpot_s(r) == pytest.approx(0.1)
    assert tpot_s(req(0, 0.0, 1.0, 1.0, 8, 1)) is None   # one chunk
    assert tpot_s(req(0, 0.0, 1.0, 3.0, 21, 3, failed=True)) == math.inf
    assert ttft_s(req(0, 0.0, 1.0, 3.0, 21, 3, failed=True)) == math.inf


def test_tails_are_over_every_request_due_in_the_window():
    run = chat_run()
    ttft = spec.load_reader("ttft_p90_ms")(run)
    want = sorted(r.first - r.due for r in run.requests[:21])[18]
    assert ttft == pytest.approx(1e3 * want)
    tpots = sorted((r.last - r.first) / 20 for r in run.requests[:20])
    assert spec.load_reader("tpot_p90_ms")(run) == pytest.approx(
        1e3 * tpots[17])                   # 20 multi-chunk outputs
    assert spec.load_reader("engine.queue_wait_ms")(run) == pytest.approx(
        100.0)


def test_a_failed_request_counts_in_the_tail():
    run = chat_run()
    for r in run.requests[:3]:
        r.failed = True
    assert spec.load_reader("ttft_p90_ms")(run) == math.inf


def test_rates_take_all_the_work_over_the_whole_window():
    run = RunRecord(kind="serve", seconds=50.0, window_s=50.4,
                    tokens_in_window=10080, peaks=PEAKS)
    assert spec.load_reader("output_tokens_per_s")(run) == pytest.approx(200)
    tr = RunRecord(kind="train", seconds=50.0, window_s=51.2, steps=48,
                   tokens_per_step=40960, peaks=PEAKS)
    tps = 48 * 40960 / 51.2
    assert spec.load_reader("train_tokens_per_s")(tr) == pytest.approx(tps)
    assert spec.load_reader("train_tokens_per_s")(run) is None


def test_traced_readers_keep_to_the_traced_span():
    """Collecting a trace stalls the window after it, so the per-layer
    readers on the host clock read only the traced span."""
    tr = RunRecord(kind="train", seconds=50.0, window_s=60.0, steps=48,
                   tokens_per_step=40960, peaks=PEAKS, traced_until=20.5,
                   traced_work={"steps": 28, "model_flops_per_token": 1e9})
    assert spec.load_reader("train.mfu")(tr) == pytest.approx(
        100 * (28 * 40960 / 20.5) * 1e9 / PEAKS["bf16_flops_per_s"])
    run = chat_run()
    run.traced_until = 10.0
    run.requests[9].admitted = 40.0         # due in the span, waits it out
    for r in run.requests[10:]:
        r.admitted = r.due + 30.0          # the stall after the trace
    assert spec.load_reader("engine.queue_wait_ms")(run) == pytest.approx(
        100.0)
    run.traced_work = {"decode_tokens": 5, "decode_model_flops": 1e13,
                       "prefill_model_flops": 5e12}
    assert spec.load_reader("serve.mfu")(run) == pytest.approx(
        100 * 1.5e12 / PEAKS["bf16_flops_per_s"])


def test_device_readers_stay_silent_without_a_trace():
    run = chat_run()
    for name in ("prefill.device_ms_per_request", "decode.step_ms",
                 "kernel.flash_decode_roofline", "kernel.flash_fwd_roofline"):
        assert spec.load_reader(name)(run) is None
