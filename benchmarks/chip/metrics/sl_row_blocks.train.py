"""Row blocks per SL kernel call: the mean ``row_blocks`` of the
program's ``sl.row_blocks`` instants, which ``kernels/ops.sl_matmul`` and
``kernels/ops.sddmm`` record each time a call is traced (one per
``sl_matmul`` forward and dx and per ``sddmm`` dv in the step), from the
process recorder of ``repro.obs``. Each weight tile is built, or
gathered, once per row block, so 1 means once per call. Read from the
instants recorded up to the end of the window, the last ``ctx["steps"]``
``train.step`` spans. Nothing is read where the program records no such
instants."""


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
    blocks = [e["args"]["row_blocks"] for e in events
              if e["name"] == "sl.row_blocks" and e["ts"] <= end]
    return sum(blocks) / len(blocks) if blocks else None
