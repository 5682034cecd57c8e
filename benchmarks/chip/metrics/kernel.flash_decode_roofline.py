"""The Pallas decode attention kernel's share of its roofline: the least
time for the attention of every token delivered in the traced span (kv
positions each row may attend to, k and v read once) over the trace time of
the Pallas ops inside the generate program."""
from benchmarks.chip.roofline import share
from benchmarks.chip.trace import kernel_time


def read(run):
    work = run.traced_work
    if not run.trace or not work or not work.get("decode_tokens"):
        return None
    t = kernel_time(run.trace, "generate_step")
    if t <= 0:
        return None
    return share(work["decode_attn_flops"], work["decode_attn_bytes"], t,
                 run.peaks)[0]
