"""The Pallas flash forward kernel's share of its roofline inside the train
step: each execution's causal work (the batch's query-key pairs of one
layer, q, k, v read and the output and log-sum-exp written once) times the
executions of the ``pallas:flash_fwd`` op that the trace shows, over their
trace time. The backward kernel is not counted here. Silent where the step
has no such op."""
from benchmarks.chip.roofline import share
from benchmarks.chip.trace import kernel_count, kernel_time

MODULE = "train_step"
PREFIX = "pallas:flash_fwd"


def read(run):
    if not run.trace:
        return None
    t = kernel_time(run.trace, MODULE, PREFIX)
    n = kernel_count(run.trace, MODULE, PREFIX)
    if t <= 0 or n <= 0:
        return None
    return share(n * run.work["attn_call_flops"], n * run.work["attn_call_bytes"],
                 t, run.peaks)[0]
