"""A tiny copy of the Mellum2 train cell for CPU tests: four layers in one
period (three windowed, one full with YaRN), two of eight experts held,
top 4, at smoke widths."""
from __future__ import annotations

import copy

from chipbench_tiny import ROOT  # noqa: F401  (puts the checkout on the path)
from benchmarks.chip import spec

MOE_TRAIN = "mellum2-12b-a2.5b-4l.train-seq4k"
# widths at smoke size; heads x head_dim (4 x 32) is not d_model (64), as
# in the published block
TINY_MODEL = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
                  head_dim=32, d_ff=32, vocab_size=512, num_experts=8,
                  experts_per_token=4, experts_held=2, expert_offset=2,
                  sliding_window=16, yarn_original_max_positions=32)


def tiny_moe_cell(**model) -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(MOE_TRAIN))
    c.config["model"].update(TINY_MODEL, **model)
    c.traffic.update(batch=4, seq_len=64, pool_batches=8)
    return c
