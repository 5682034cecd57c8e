"""Multi-device behaviour via subprocesses (8 forced host devices), so the
main test process keeps the true 1-device platform."""
import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "distributed_worker.py")


def _run(mode: str, timeout: int = 420) -> None:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, WORKER, mode],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, (
        f"{mode} failed:\nSTDOUT:{proc.stdout[-3000:]}\n"
        f"STDERR:{proc.stderr[-3000:]}")
    assert f"PASS {mode}" in proc.stdout


def test_sharding_invariance():
    _run("sharding_invariance")


def test_dappa_distributed():
    _run("dappa_distributed")


def test_proteus_psum():
    _run("proteus_psum")


def test_proteus_train_step():
    _run("proteus_train_step")


def test_mini_dryrun():
    _run("mini_dryrun", timeout=560)


def test_pipeline_parallel():
    _run("pipeline")


def test_pallas_kernels_per_batch_shard():
    _run("pallas_batch_shard")


def test_chip_smoke_data_parallel_phase():
    _run("chip_smoke_dp")
