"""Backend compiles (or persistent-cache loads) inside the measured
window: the program's ``jax.compile`` events, recorded by the process
recorder of ``repro.obs``, that overlap the window, which runs from the
start of its first ``train.step`` span to the end of its last. The window
is the last ``ctx["steps"]`` ``train.step`` spans, since the harness
compiles again after it. Reads 0 while the window's spans exist and no
compile overlaps them; nothing where the program records no such spans."""


def window(ctx):
    """The recorder's events and the window's ``train.step`` spans, in a
    traced run (``ctx["trace"]``), where the harness reads them."""
    if ctx.get("job") != "train" or not ctx.get("steps") \
            or not ctx.get("trace"):
        return None
    try:
        from repro.obs import get_trace
    except ImportError:
        return None
    events = get_trace().events
    steps = [e for e in events if e["name"] == "train.step"]
    if len(steps) < ctx["steps"]:
        return None
    return events, steps[-ctx["steps"]:]


def read(ctx):
    found = window(ctx)
    if found is None:
        return None
    events, steps = found
    lo, hi = steps[0]["ts"], steps[-1]["ts"] + steps[-1]["dur"]
    return float(sum(1 for e in events if e["name"] == "jax.compile"
                     and e["ts"] < hi and e["ts"] + e["dur"] > lo))
