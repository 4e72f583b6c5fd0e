"""Model FLOPs utilisation of the whole training step: SLTrain's own FLOPs
per token (factored linears, the head over the vocabulary held here, and
causal attention; recompute and the embedding gather count nothing), times
the tokens of the steps in the traced window, over the window's length and
the chip's peak bf16 FLOP/s."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("job") != "train" or not tr or not ctx.get("tokens"):
        return None
    chips = max(tr["devices"], 1)
    flops = ctx["flops_per_token"] * ctx["tokens"]
    return 100.0 * flops / tr["window_s"] / (
        chips * ctx["peaks"]["bf16_flops_per_s"])
