"""Mean of the trainer's own ``train.phase_ms{phase=data}`` span (host
clock: the next batch from the data pipeline and its transfer) over the
steps of the window."""


def read(ctx):
    if ctx.get("job") != "train":
        return None
    return ctx.get("data_ms")
