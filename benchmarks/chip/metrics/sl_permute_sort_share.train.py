"""Share of the SL linears' permutations between flat ``v`` and tile
order that run as key sorts: of the program's ``sl.permute`` instants,
which ``kernels/ops.sl_linear`` records each time a pass is traced (one
per forward, ``fwd``, and per backward, ``dv``), the percentage whose
``how`` is ``sort`` rather than an XLA ``gather`` or ``scatter``. Read
from the process recorder of ``repro.obs``, from the instants recorded
up to the end of the window, the last ``ctx["steps"]`` ``train.step``
spans. Nothing is read where the program records no such instants."""


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
    end = steps[-1]["ts"] + steps[-1]["dur"]
    hows = [e["args"]["how"] for e in events
            if e["name"] == "sl.permute" and e["ts"] <= end]
    return 100.0 * hows.count("sort") / len(hows) if hows else None
