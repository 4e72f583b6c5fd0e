"""Temporary device memory of the compiled training step program, from
``compiled.memory_analysis().temp_size_in_bytes``, in GiB."""


def read(ctx):
    if ctx.get("job") != "train" or ctx.get("step_temp_bytes") is None:
        return None
    return ctx["step_temp_bytes"] / float(1 << 30)
