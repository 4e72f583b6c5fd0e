"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-operation device time and idle gaps labelled by what the host did.

The trace is read with ``jax.profiler.ProfileData`` into plain
:class:`Plane` / :class:`Line` / :class:`Event` records, so the reduction
can be tested on a synthesized trace as well as on a recorded one.

* Device planes are the planes named ``/device:<ACCELERATOR>:<n>``; the
  operations that ran on a device are the events of its ``XLA Ops`` line.
* The window is the host span named ``WINDOW`` that the harness opens
  around the measured work (a ``jax.profiler.TraceAnnotation``).
* Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices that ran anything. Per-op time counts
  only the innermost operations, so a loop and its body are not counted
  twice.
* Each of the longest idle gaps is labelled by the innermost host event
  that covers its midpoint, on any host thread.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
#: the longest idle gaps that are labelled and summed by label
LABELLED_GAPS = 500


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Line:
    name: str
    events: List[Event]


@dataclass
class Plane:
    name: str
    lines: List[Line]


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            evs = [Event(e.name, float(e.start_ns), float(e.duration_ns),
                         dict(e.stats)) for e in ln.events]
            lines.append(Line(ln.name, evs))
        planes.append(Plane(p.name, lines))
    return planes


def is_device_plane(name: str) -> bool:
    head, _, idx = name.rpartition(":")
    return head.startswith("/device:") and idx.isdigit() and \
        "CUSTOM" not in head


def device_ops(planes: List[Plane]) -> Dict[str, List[Event]]:
    out = {}
    for p in planes:
        if is_device_plane(p.name):
            evs = [e for ln in p.lines if ln.name == OPS_LINE
                   for e in ln.events]
            if evs:
                out[p.name] = evs
    return out


def host_events(planes: List[Plane]) -> List[Event]:
    return [e for p in planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]


def window_of(planes: List[Plane]) -> Tuple[float, float]:
    spans = [e for e in host_events(planes) if e.name == WINDOW]
    if not spans:
        raise ValueError(f"trace has no host span named {WINDOW!r}")
    return (min(e.start_ns for e in spans), max(e.end_ns for e in spans))


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(merged, lo: float, hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def labeller(hosts: List[Event]):
    """Function of a time giving the name of the shortest host event that
    covers it (the innermost open span), or ``"(no host span)"``."""
    import numpy as np
    hosts = [e for e in hosts if e.name != WINDOW]
    start = np.array([e.start_ns for e in hosts], np.float64)
    end = np.array([e.end_ns for e in hosts], np.float64)
    dur = end - start

    def label(t_ns: float) -> str:
        cover = np.flatnonzero((start <= t_ns) & (end >= t_ns))
        if cover.size == 0:
            return "(no host span)"
        return hosts[int(cover[np.argmin(dur[cover])])].name
    return label


def op_name(e: Event) -> str:
    """A device operation's short name: the TPU trace names an op by its
    HLO text, ``%sl_matmul.167 = f32[...] custom-call(...)``; this keeps
    ``sl_matmul.167``. A Pallas kernel's op is named after its kernel."""
    name = e.name.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def base_name(op: str) -> str:
    """``sl_matmul.167`` -> ``sl_matmul``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def leaves(events: List[Event]) -> List[Event]:
    """The operations that contain no other: on one line of a device's
    ops, an op is either nested in another (a ``while`` loop's body in the
    loop) or disjoint from it, so an op is a parent exactly when the next
    op to start starts before it ends."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1].start_ns >= e.end_ns]


def reduce(planes: List[Plane], top: int = 10) -> dict:
    """Busy and window seconds, per-op device seconds and the breakdown."""
    lo, hi = window_of(planes)
    per_dev = device_ops(planes)
    busy, idle_all, totals = [], [], {}
    for evs in per_dev.values():
        merged = union(((e.start_ns, e.end_ns) for e in evs), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        idle_all.extend(gaps(merged, lo, hi))
        for e in leaves(evs):
            if lo <= e.start_ns and e.end_ns <= hi:
                name = op_name(e)
                totals[name] = totals.get(name, 0.0) + e.dur_ns * 1e-9
    label = labeller(host_events(planes))
    labelled: Dict[str, float] = {}
    for s, e in sorted(idle_all, key=lambda g: g[0] - g[1])[:LABELLED_GAPS]:
        name = label((s + e) / 2)
        labelled[name] = labelled.get(name, 0.0) + (e - s) * 1e-9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        "devices": len(per_dev),
        "op_seconds": totals,
        "breakdown": {
            "device_ops": [[k, v] for k, v in ranked[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(
                labelled.items(), key=lambda kv: -kv[1])[:top]],
        },
    }
