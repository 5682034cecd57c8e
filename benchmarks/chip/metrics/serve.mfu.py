"""The whole serving step's share of the chip's bf16 peak over the traced
span: model operations of the work done in it, over its host time, over the
peak. Work counts valid positions only: each admitted request's prefill
(two operations per weight of every matrix product per prompt token, and
the causal attention over the prompt), and each delivered token's decode row
(the same per token, and attention over the positions it may attend to)."""


def read(run):
    work, span = run.traced_work, run.traced_until
    if run.kind != "serve" or not work or not work.get("decode_tokens") \
            or not span:
        return None
    flops = work["decode_model_flops"] + work.get("prefill_model_flops", 0.0)
    return 100.0 * flops / span / (run.chips * run.peaks["bf16_flops_per_s"])
