"""The whole train step's share of the chip's bf16 peak over the traced
span: forward and backward operations per token (six per weight of every
matrix product, and three times the causal attention forward) times the
tokens stepped in the span over its host time, over chips times the peak.
Rematerialised work is not counted."""


def read(run):
    work, span = run.traced_work, run.traced_until
    if run.kind != "train" or not work or not work.get("steps") or not span:
        return None
    tps = work["steps"] * run.tokens_per_step / span
    return 100.0 * tps * work["model_flops_per_token"] / (
        run.chips * run.peaks["bf16_flops_per_s"])
