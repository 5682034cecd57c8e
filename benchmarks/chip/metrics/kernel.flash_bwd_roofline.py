"""The Pallas flash backward kernel's share of its roofline inside the train
step. One call is one layer's backward, and it counts 2.5 times the
forward's causal work: five matrix products over the causal pairs (the
scores recomputed, dP, dV, dQ and dK) where the forward has two. It counts
twice the forward's bytes: it reads q, k, v and dO and writes dq, dk and dv
(with the log-sum-exp and delta rows), where the forward reads three and
writes one. The time is that of every ``pallas:flash_bwd*`` op in the step;
the calls are the executions of the op that runs once a call,
``pallas:flash_bwd_dkv`` where dq has a kernel of its own, else
``pallas:flash_bwd``. Silent where the step has no such op."""
from benchmarks.chip.roofline import share
from benchmarks.chip.trace import kernel_time, ops_in

MODULE = "train_step"
PREFIX = "pallas:flash_bwd"
ONCE_A_CALL = ("pallas:flash_bwd_dkv", "pallas:flash_bwd")


def read(run):
    if not run.trace:
        return None
    t = kernel_time(run.trace, MODULE, PREFIX)
    calls = ops_in(run.trace, MODULE, PREFIX, "op_in_module_n")
    n = next((calls[k] for k in ONCE_A_CALL if calls.get(k)), 0.0)
    if t <= 0 or n <= 0:
        return None
    return share(2.5 * n * run.work["attn_call_flops"],
                 2.0 * n * run.work["attn_call_bytes"], t, run.peaks)[0]
