"""Tiny copies of the benchmark's cells for CPU tests: the same files and
code paths at smoke widths, with short windows."""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness, roofline, spec  # noqa: E402

PEAKS = roofline.peaks_for("TPU v5 lite")
TRAIN = "internlm2-1.8b-6l.train-seq1k"
# No serving cell is committed yet: the serving tests drive one made from
# the serving configuration and the offline traffic file, and a chat one
# from the same with Poisson arrivals, as a later chat mix would be.
OFFLINE = "internlm2-1.8b.offline-conv"
CHAT = "chat"


def serving_cell() -> spec.Cell:
    return spec.Cell(
        name=OFFLINE, chips=1,
        config=spec.load_json(spec.HERE / "configs" / "internlm2-1.8b.json"),
        traffic=spec.load_json(spec.HERE / "traffic" / "offline-conv.json"),
        limits={"limits": {}},
        end_to_end=[{"name": "output_tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}])


def chat_traffic(rate: float = 3.0) -> dict:
    t = copy.deepcopy(serving_cell().traffic)
    t["arrivals"] = {"process": "poisson", "rate_per_s": rate}
    return t


def tiny_cell(workload: str) -> spec.Cell:
    """The cell with its widths, depth, vocabulary and batch cut to smoke
    size; every other setting as committed."""
    if workload in (OFFLINE, CHAT):
        c = serving_cell()
        if workload == CHAT:
            c.traffic = chat_traffic()
    else:
        c = copy.deepcopy(spec.load_cell(workload))
    c.config["model"].update(num_layers=2, d_model=64, num_heads=4,
                             num_kv_heads=2, head_dim=16, d_ff=128,
                             vocab_size=512)
    if c.kind == "serve":
        c.traffic["engine"].update(slots=4, prompt_bucket=32, max_new=24,
                                   chunk=4, kv_page_size=8)
        c.traffic["prompt_tokens"] = {"dist": "log_uniform", "min": 4,
                                      "max": 32}
        out = c.traffic["output_tokens"]
        c.traffic["output_tokens"] = (
            {"dist": "fixed", "value": 24} if out["dist"] == "fixed"
            else {"dist": "log_uniform", "min": 9, "max": 24})
        if c.traffic["arrivals"]["process"] == "poisson":
            c.traffic["arrivals"]["rate_per_s"] = 6.0
        else:
            c.traffic["arrivals"].update(requests=64, block=16)
    else:
        c.traffic.update(batch=4, seq_len=64, pool_batches=8)
    return c


def run(cell: spec.Cell, seed: int = 2 ** 31 + 5, seconds: float = 2.0,
        **kw):
    import jax  # noqa: PLC0415

    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                            devices=jax.devices()[:1],
                            t_start=time.perf_counter(), peaks=PEAKS, **kw)
