"""Deterministic fault-injection harness (DESIGN §7: failure drills).

A :class:`ChaosEngine` parses a compact spec — ``kind@when[:arg]``,
comma-separated — into a schedule of faults that fire deterministically
on the trainer's step counter or the serve engine's tick clock:

=============  =====================================================
``kill@N``         raise :class:`ChaosKill` (``SystemExit`` with exit
                   code 43) before step N executes — a hard process
                   kill the relaunch must recover from
``nonfinite@N``    poison step N's loss with a NaN scale factor
                   (``batch["chaos_scale"]``) so non-finite values
                   propagate through the REAL vjp into the gradients
``ckpt_corrupt@N`` flip bytes in the newest checkpoint's
                   ``arrays.npz`` at step N (restore must detect the
                   damage and fall back to an intact step)
``data_corrupt@N`` overwrite batch tokens with out-of-range values at
                   step N (host-side validation must drop the batch)
``straggler@N:MS`` sleep MS milliseconds inside step N's timed window
                   (the step watchdog must flag it)
``stall@T:K``      serve: freeze one active slot for K engine ticks
                   starting at tick T (deadlines/drain must cope)
=============  =====================================================

Every fault fires AT MOST ONCE per engine instance (``@N`` means "the
first opportunity at or after N") — so steps re-executed after a
rollback are not re-poisoned, matching a transient hardware fault.
Randomized choices (which slot to stall) draw from a PRNG keyed on
(seed, fault time), never from global state, so a chaos run is exactly
reproducible. Injections are counted on the bound registry as
``resilience.faults_injected{kind=...}``.
"""
from __future__ import annotations

import os
import struct
import time
import zipfile
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

KINDS = ("kill", "nonfinite", "ckpt_corrupt", "data_corrupt", "straggler",
         "stall")


class ChaosKill(SystemExit):
    """Injected process kill. A ``SystemExit`` subclass so nothing up the
    stack accidentally swallows it with ``except Exception``; the exit
    code is distinct from the trainer's preemption exit (42) so harnesses
    can tell a drill from a real preemption."""

    EXIT_CODE = 43

    def __init__(self, step: int):
        super().__init__(self.EXIT_CODE)
        self.step = step


@dataclass(frozen=True)
class Fault:
    kind: str
    at: int                      # step (train) or tick (serve)
    arg: Optional[int] = None    # ms (straggler) / ticks (stall)


def corrupt_npz(path: str, *, seed: int = 0, n_bytes: int = 16) -> int:
    """Flip up to ``n_bytes`` bytes of one array's stored bytes in
    ``path`` in place (XOR 0xFF at a deterministic offset). Returns the
    offset. The flip stays inside a zip member's data: zip readers ignore
    local-header fields (timestamps, extra fields), so a flip there would
    be damage no reader can see. Used by the ``ckpt_corrupt`` fault and
    the fault-tolerance tests."""
    with zipfile.ZipFile(path) as z:
        infos = [i for i in z.infolist() if i.compress_size > 0]
    rng = np.random.default_rng(np.uint64(seed))
    info = infos[int(rng.integers(len(infos)))]
    with open(path, "r+b") as f:
        # local file header: 30 fixed bytes, then name and extra field
        f.seek(info.header_offset + 26)
        name_len, extra_len = struct.unpack("<HH", f.read(4))
        start = info.header_offset + 30 + name_len + extra_len
        n = min(n_bytes, info.compress_size)
        off = start + int(rng.integers(info.compress_size - n + 1))
        f.seek(off)
        raw = f.read(n)
        f.seek(off)
        f.write(bytes(b ^ 0xFF for b in raw))
    return off


class ChaosEngine:
    """Holds the fault schedule plus fire-once state for one run."""

    def __init__(self, faults: List[Fault], *, seed: int = 0):
        for f in faults:
            if f.kind not in KINDS:
                raise ValueError(f"unknown fault kind {f.kind!r}: expected "
                                 f"one of {KINDS}")
        self.faults = list(faults)
        self.seed = seed
        self._fired: set = set()
        self._c_injected = None   # obs counter family, set by bind()

    @classmethod
    def parse(cls, spec: str, *, seed: int = 0) -> "ChaosEngine":
        """Parse ``"kind@when[:arg],..."`` (e.g. ``"kill@3"``,
        ``"nonfinite@5,straggler@4:50"``)."""
        faults = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            try:
                kind, rest = part.split("@", 1)
                arg = None
                if ":" in rest:
                    rest, a = rest.split(":", 1)
                    arg = int(a)
                faults.append(Fault(kind.strip(), int(rest), arg))
            except ValueError as e:
                raise ValueError(
                    f"bad chaos fault {part!r}: expected kind@when[:arg] "
                    f"with kind in {KINDS}") from e
        if not faults:
            raise ValueError(f"empty chaos spec {spec!r}")
        return cls(faults, seed=seed)

    def bind(self, obs) -> None:
        """Attach an ``obs.metrics.Registry`` so injections are counted
        (``resilience.faults_injected{kind=...}``)."""
        self._c_injected = obs.counter(
            "resilience.faults_injected",
            help="chaos faults injected, by kind")

    # -- internals ----------------------------------------------------------
    def _pending(self, kind: str, now: int) -> List[Fault]:
        return [f for f in self.faults
                if f.kind == kind and f not in self._fired and f.at <= now]

    def _fire(self, fault: Fault) -> None:
        self._fired.add(fault)
        if self._c_injected is not None:
            self._c_injected.labels(kind=fault.kind).inc()

    def _rng(self, at: int) -> np.random.Generator:
        return np.random.default_rng(np.uint64(self.seed * 1_000_003 + at))

    # -- train-side hooks ---------------------------------------------------
    @property
    def wants_poison(self) -> bool:
        """True when any ``nonfinite`` fault is scheduled — the trainer
        then carries ``batch["chaos_scale"]`` EVERY step (constant pytree
        structure, one compile) and only the value turns NaN."""
        return any(f.kind == "nonfinite" for f in self.faults)

    def train_hook(self, step: int, *, ckpt_dir: Optional[str] = None) -> None:
        """Top-of-loop faults: process kill and checkpoint corruption.
        ``ckpt_corrupt`` stays pending until a published checkpoint
        actually exists."""
        if ckpt_dir is not None:
            for f in self._pending("ckpt_corrupt", step):
                npz = _latest_ckpt_npz(ckpt_dir)
                if npz is None:
                    continue
                corrupt_npz(npz, seed=self.seed + f.at)
                self._fire(f)
        for f in self._pending("kill", step):
            self._fire(f)
            raise ChaosKill(step)

    def poison_scale(self, step: int) -> float:
        """NaN when a ``nonfinite`` fault fires at ``step``, else 1.0."""
        for f in self._pending("nonfinite", step):
            self._fire(f)
            return float("nan")
        return 1.0

    def corrupt_batch(self, step: int, batch: Dict[str, np.ndarray]
                      ) -> Dict[str, np.ndarray]:
        """Overwrite a stripe of tokens with out-of-range values — the
        trainer's host-side validation must reject the batch."""
        for f in self._pending("data_corrupt", step):
            self._fire(f)
            toks = np.array(batch["tokens"], copy=True)
            rng = self._rng(f.at)
            rows = rng.integers(0, toks.shape[0],
                                size=max(1, toks.shape[0] // 2))
            toks[rows, : max(1, toks.shape[1] // 4)] = -(7 + f.at)
            batch = dict(batch)
            batch["tokens"] = toks
        return batch

    def straggle(self, step: int) -> None:
        """Sleep inside the step's timed window (watchdog currency)."""
        for f in self._pending("straggler", step):
            self._fire(f)
            time.sleep((f.arg or 100) / 1e3)

    # -- serve-side hook ----------------------------------------------------
    def serve_hook(self, engine) -> None:
        """Per-tick hook (``ServeEngine(tick_hook=chaos.serve_hook)``):
        ``stall@T:K`` freezes one active slot — chosen by the keyed PRNG —
        for K ticks at the first tick ≥ T with any slot active."""
        for f in self._pending("stall", engine.clock):
            if engine.paged:
                slots = engine.sched.active_slots
            else:
                slots = [s for s in range(engine.n_slots)
                         if engine.slot_req[s] is not None]
            if not slots:
                continue      # stays pending until a slot is active
            slot = int(slots[int(self._rng(f.at).integers(len(slots)))])
            engine.stall_slot(slot, f.arg or 8)
            self._fire(f)


def _latest_ckpt_npz(ckpt_dir: str) -> Optional[str]:
    """Newest published checkpoint's arrays.npz (None when none yet)."""
    try:
        names = os.listdir(ckpt_dir)
    except FileNotFoundError:
        return None
    steps = []
    for d in names:
        if d.startswith("step_") and not d.endswith(".tmp"):
            try:
                steps.append(int(d[5:]))
            except ValueError:
                pass
    if not steps:
        return None
    path = os.path.join(ckpt_dir, f"step_{max(steps):08d}", "arrays.npz")
    return path if os.path.exists(path) else None
