"""Milliseconds per step in which the device waits on the host loop: the
mean, over consecutive steps of the window, of the end of the next step's
``train.dispatch`` span less the end of this step's ``train.sync`` span.
Between the two the host reads back the step's metrics, leaves and enters
``Trainer.run``, takes the next batch and enqueues the next step. The
spans are the trainer's, children of its ``train.step`` spans by parent
id, from the process recorder of ``repro.obs``; the window is the last
``ctx["steps"]`` ``train.step`` spans. Nothing is read where the program
records no such spans or the window holds one step."""


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
    child = {(e["parent"], e["name"]): e["ts"] + e["dur"] for e in events
             if "parent" in e}
    gaps = []
    for this, nxt in zip(steps, steps[1:]):
        sync = child.get((this.get("id"), "train.sync"))
        dispatch = child.get((nxt.get("id"), "train.dispatch"))
        if sync is None or dispatch is None:
            return None
        gaps.append(dispatch - sync)
    return sum(gaps) / len(gaps) * 1e-3 if gaps else None
