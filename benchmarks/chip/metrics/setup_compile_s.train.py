"""Seconds of set-up spent making programs: the union of the program's
``jax.trace``, ``jax.lower`` and ``jax.compile`` events (JAX's jit traces,
lowerings, and backend compiles or persistent-cache loads, recorded by the
process recorder of ``repro.obs``) that end before the window's first
``train.step`` span. The union, not the sum: a jit traced while its caller
is traced lies inside the caller's event. The window is the last
``ctx["steps"]`` ``train.step`` spans, since the harness compiles again
after the window. Nothing is read where the program records no such
spans."""

COMPILE_EVENTS = ("jax.trace", "jax.lower", "jax.compile")


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
    start = steps[0]["ts"]
    spans = sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                   if e["name"] in COMPILE_EVENTS
                   and e["ts"] + e["dur"] <= start)
    covered, reach = 0.0, float("-inf")
    for s, e in spans:
        if e > reach:
            covered += e - max(s, reach)
            reach = e
    return covered * 1e-6
