"""The Pallas flash forward kernel's share of its roofline inside the train
step: each execution's causal work (the batch's query-key pairs of one
layer, q, k, v read and the output and log-sum-exp written once) times the
executions the trace shows, over their trace time."""
from benchmarks.chip.roofline import share
from benchmarks.chip.trace import kernel_count, kernel_time


def read(run):
    if not run.trace:
        return None
    t = kernel_time(run.trace, "train_step")
    n = kernel_count(run.trace, "train_step")
    if t <= 0 or n <= 0:
        return None
    return share(n * run.work["attn_call_flops"], n * run.work["attn_call_bytes"],
                 t, run.peaks)[0]
