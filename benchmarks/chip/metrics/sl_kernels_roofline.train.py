"""Share of the SL kernels' roofline in the training step: the sum over
their calls of each call's least time (the larger of its SLTrain FLOPs over
peak FLOP/s and its bytes over peak bandwidth; chipbench/work.py) over the
sum of those kernels' device time in the traced window. The work is the
factored layer's, never the densified matrix's, so the share cannot pass
100% whatever implements the linear. Nothing is read where the trace holds
no kernel of the step."""

from chipbench.tracing import base_name
from chipbench.work import least_seconds

#: the trace names a Pallas kernel's op after the kernel
KERNELS = ("sl_matmul", "sddmm")


def read(ctx):
    tr = ctx.get("trace")
    calls = ctx.get("sl_calls_per_step")
    if ctx.get("job") != "train" or not tr or not calls or not ctx["steps"]:
        return None
    seconds = sum(t for name, t in tr["op_seconds"].items()
                  if base_name(name) in KERNELS)
    if seconds <= 0:
        return None
    least = sum(least_seconds(c, ctx["peaks"]) for c in calls)
    return 100.0 * ctx["steps"] * least / seconds
