"""Seconds of set-up spent on the SL linears' tile tables: the sum of the
program's ``sl.tile_tables`` spans (host build of the tile-CSR index
tables and their transfer, in ``kernels/ops.prepare_tile_consts``) that
end before the window's first ``train.step`` span, from the process
recorder of ``repro.obs``. The window is the last ``ctx["steps"]``
``train.step`` spans. Nothing is read where the program records no such
spans."""


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
    spans = [e["dur"] for e in events if e["name"] == "sl.tile_tables"
             and e["ts"] + e["dur"] <= start]
    return sum(spans) * 1e-6 if spans else None
