"""Training loop: logging, checkpoint/restart, preemption handling,
straggler watchdog, fault injection + divergence recovery (DESIGN §7).

The loop is deliberately framework-grade rather than script-grade:
  * resume-from-latest is the default (idempotent relaunch == restart),
  * SIGTERM/SIGINT triggers a synchronous checkpoint then exit(42) so a
    cluster scheduler can requeue the job (preemption safety),
  * a per-step deadline watchdog flags stragglers; the mitigation hook
    (re-dispatching the slow host's shard) is pluggable — on a single host
    we log and continue, on a fleet the launcher wires in spares,
  * ``fault_hook(step)`` lets tests inject crashes at exact steps to prove
    kill/resume bit-exactness (tests/test_fault_tolerance.py).

Resilience (repro.resilience; tests/test_fault_tolerance.py):
  * **fault matrix** — pass ``chaos=ChaosEngine.parse(spec)`` (launcher
    flag ``--chaos``, e.g. ``"kill@3,nonfinite@5,straggler@4:50"``) and
    the loop deterministically injects process kills (exit 43),
    NaN-poisoned losses, corrupted checkpoint bytes, corrupted data
    batches, and straggler sleeps at exact steps,
  * **escalation policy** — every step's jitted program carries a
    non-finite gate (train/step.py, train/perlayer.py): a NaN/inf loss or
    gradient never reaches the weights (the update is skipped bit-exactly
    in-jit) and is reported via ``metrics["nonfinite"]``. After
    ``max_skips`` consecutive skipped steps the trainer ROLLS BACK to the
    newest intact checkpoint and skips the data cursor forward
    (``rollback_data_skip`` batches, doubling per rollback — the retry
    backoff); after ``max_rollbacks`` rollbacks (``--max-rollbacks``) it
    gives up loudly,
  * **corrupt batches** — host-side token validation drops out-of-range
    batches and advances the cursor (bounded retries),
  * **checksummed checkpoints** — restore verifies per-array CRC32s +
    the manifest digest and falls back to the newest intact step
    (ckpt/checkpoint.py), so a flipped byte costs one ckpt_every of
    progress, not the run.
  Every recovery event lands on the obs registry:
  ``resilience.faults_injected{kind}``, ``resilience.nonfinite_steps``,
  ``resilience.rollbacks``, ``resilience.bad_batches``, plus
  ``resilience.rollback``/``resilience.restore`` trace spans.
"""
from __future__ import annotations

import contextlib
import functools
import signal
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import CheckpointCorruptError, CheckpointManager
from repro.configs.base import TrainConfig
from repro.core import relora as relora_lib
from repro.data.pipeline import SyntheticC4
from repro.models import registry
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import optimizers
from repro.train import step as step_lib


def _make_relora_merge(cfg):
    """ReLoRA restart (paper eq. (1) / baseline [32]): at each period end,
    merge BA into W0, re-init the factors, and ZERO the factors' Adam
    moments (the optimizer-state reset the paper's schedule requires).

    The merge scale is alpha / r_eff PER MATRIX (r_eff = B.shape[-1], the
    rank Builder.linear actually allocated after the min(d_in, d_out)//2
    cap) — the same convention apply_linear uses in the forward. A global
    alpha/rank here would merge small (capped) matrices at the wrong
    magnitude."""
    alpha = cfg.param.alpha

    def merge(params, opt_state, key):
        is_relora = lambda t: isinstance(t, dict) and \
            {"W0", "B", "A"} <= set(t.keys())

        leaves_done = []

        def walk(t, k):
            if is_relora(t):
                k, sub = jax.random.split(k)
                merged = relora_lib.merge(t, sub, alpha / t["B"].shape[-1])
                leaves_done.append(True)
                return merged, k
            if isinstance(t, dict):
                out = {}
                for name in t:
                    out[name], k = walk(t[name], k)
                return out, k
            return t, k

        new_params, _ = walk(params, key)

        new_opt = dict(opt_state)
        if "mu" in opt_state:
            def reset(tree):
                def go(m, p):
                    if isinstance(p, dict) and {"W0", "B", "A"} <= set(p):
                        out = dict(m)
                        out["B"] = jnp.zeros_like(m["B"])
                        out["A"] = jnp.zeros_like(m["A"])
                        return out
                    if isinstance(p, dict):
                        return {n: go(m[n], p[n]) for n in p}
                    return m
                return go(tree, params)
            new_opt["mu"] = reset(opt_state["mu"])
            new_opt["nu"] = reset(opt_state["nu"])
        return new_params, new_opt

    return merge


@dataclass
class TrainerState:
    params: Any
    opt_state: Any
    consts: Any
    step: int = 0


@dataclass
class StepTimeWatchdog:
    """Flags steps slower than ``factor`` × the rolling median (straggler
    detection). The *response* is a callback so deployments can re-dispatch
    the straggler's data shard to a hot spare (DESIGN §7)."""
    factor: float = 3.0
    window: int = 32
    on_straggler: Optional[Callable[[int, float, float], None]] = None
    times: List[float] = field(default_factory=list)
    flagged: List[int] = field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        if len(self.times) > self.window:
            self.times.pop(0)
        med = float(np.median(self.times))
        slow = len(self.times) >= 8 and dt > self.factor * med
        if slow:
            self.flagged.append(step)
            if self.on_straggler:
                self.on_straggler(step, dt, med)
        return slow


class Trainer:
    def __init__(self, tc: TrainConfig, *, mesh=None, log_fn=print,
                 fault_hook: Optional[Callable[[int], None]] = None,
                 chaos=None, max_skips: int = 2, max_rollbacks: int = 2,
                 rollback_data_skip: int = 1,
                 obs: Optional[obs_metrics.Registry] = None,
                 trace: Optional[obs_trace.Trace] = None,
                 metrics_out: Optional[str] = None):
        self.tc = tc
        self.mesh = mesh
        self.log = log_fn
        self.fault_hook = fault_hook
        # -- resilience policy (module docstring: escalation policy) --
        self.chaos = chaos
        self.max_skips = max_skips
        self.max_rollbacks = max_rollbacks
        self.rollback_data_skip = rollback_data_skip
        self._skip_streak = 0
        self._rollbacks = 0
        self.cfg = tc.model
        self.api = registry.get_api(self.cfg)
        self.optimizer = optimizers.make(tc.optim)
        self.ckpt = CheckpointManager(tc.ckpt_dir, keep=tc.keep_ckpts)
        self.data = SyntheticC4(self.cfg.vocab_size, tc.seq_len,
                                tc.global_batch, seed=tc.seed)
        self.watchdog = StepTimeWatchdog()
        self._preempted = False
        self.metrics_history: List[Dict[str, float]] = []

        # -- observability (repro.obs): own registry per trainer so
        # side-by-side runs (sweeps, tests) never share counters; pass a
        # shared one to aggregate. Spans go to the process recorder unless
        # a trace is passed (Trace(enabled=False) turns them off).
        self.obs = obs if obs is not None else obs_metrics.Registry()
        self.trace = trace if trace is not None else obs_trace.get_trace()
        self.metrics_out = metrics_out
        self._c_steps = self.obs.counter("train.steps")
        self._c_tokens = self.obs.counter(
            "train.tokens", help="tokens consumed (global batch x seq)")
        self._g_loss = self.obs.gauge("train.loss")
        self._g_lr = self.obs.gauge("train.lr")
        self._g_gnorm = self.obs.gauge("train.grad_norm")
        self._g_tps = self.obs.gauge(
            "train.tokens_per_sec",
            help="tokens / (data + dispatch + sync) time of the step")
        self._h_step = self.obs.histogram(
            "train.step_ms", buckets=obs_metrics.ms_buckets())
        phase_h = self.obs.histogram(
            "train.phase_ms", buckets=obs_metrics.ms_buckets(),
            help="per-step phase split: data | dispatch | sync")
        self._h_phase = {k: phase_h.labels(phase=k)
                         for k in ("data", "dispatch", "sync")}
        self._c_nonfinite = self.obs.counter(
            "resilience.nonfinite_steps",
            help="steps whose update was skipped (non-finite loss/grads)")
        self._c_rollbacks = self.obs.counter(
            "resilience.rollbacks",
            help="rollbacks to the newest intact checkpoint")
        self._c_bad_batches = self.obs.counter(
            "resilience.bad_batches",
            help="corrupt data batches dropped by host-side validation")
        if self.chaos is not None:
            self.chaos.bind(self.obs)

        self._train_step = self._build_train_step(grad_specs=None)
        self._relora_merge = jax.jit(_make_relora_merge(self.cfg)) \
            if self.cfg.param.mode == "relora" else None

    def _build_train_step(self, *, grad_specs):
        """Build the jitted step for the configured update_mode.

        Called once at construction (grad_specs=None) and again from
        ``_place`` when ``sharding.fsdp`` is set — the fsdp param specs
        only exist once the param tree does, and the step closes over
        them to pin gradients to the sharded layout (reduce-scatter)."""
        tc = self.tc
        # params and optimizer state are donated: each step's new state
        # takes the old one's buffers, so one copy of the state is resident
        # (the loop never reads a state it has stepped past)
        jit = functools.partial(jax.jit, donate_argnums=(0, 1))
        if tc.sharding.update_mode == "per_layer":
            from repro.train import perlayer
            return jit(perlayer.make_perlayer_train_step(
                self.cfg, self.api, self.optimizer,
                remat=tc.sharding.remat,
                grad_accum=tc.sharding.grad_accum,
                grad_specs=grad_specs))
        if tc.sharding.update_mode != "global":
            raise ValueError(f"unknown update_mode "
                             f"{tc.sharding.update_mode!r}: expected "
                             f"'global' or 'per_layer'")
        if tc.sharding.pod_grad_compression and self.mesh is not None \
                and "pod" in self.mesh.axis_names:
            # int8-compressed cross-pod DP (dist/compression.py); wire
            # counters land on this trainer's registry -> metrics JSONL
            return jit(step_lib.make_compressed_dp_step(
                self.cfg, self.api, self.optimizer, self.mesh,
                obs=self.obs))
        return jit(step_lib.make_train_step(
            self.cfg, self.api, self.optimizer,
            remat=tc.sharding.remat, grad_accum=tc.sharding.grad_accum,
            grad_specs=grad_specs))

    # -- state ----------------------------------------------------------------
    def init_state(self) -> TrainerState:
        key = jax.random.PRNGKey(self.tc.seed)
        params, consts = self.api.init(self.cfg, key, seed=self.tc.seed)
        if self.mesh is None:
            return TrainerState(params, self.optimizer.init(params), consts)
        # on a mesh the optimizer state is built already sharded: a
        # model's whole state need not fit on one device (LLaMA-7B's does
        # not fit on one v5e chip)
        from repro.dist import sharding as dist_sharding
        p_specs, o_specs, c_specs = self._specs(params, consts)
        params = dist_sharding.place(params, self.mesh, p_specs)
        opt_state = jax.jit(
            self.optimizer.init,
            out_shardings=dist_sharding.named_shardings(self.mesh, o_specs)
        )(params)
        return TrainerState(
            params, opt_state,
            dist_sharding.place(consts, self.mesh, c_specs))

    def _specs(self, params, consts):
        """(param, optimizer-state, const) PartitionSpec trees for the
        mesh, per the dist.sharding spec engine. Optimizer moments inherit
        the matching param leaf's spec; with ``sharding.fsdp`` every tree
        also shards over the fsdp axis."""
        from repro.dist import sharding as dist_sharding
        sh = self.tc.sharding
        fsdp_axes = (sh.fsdp_axis,) if sh.fsdp else ()
        p_specs = dist_sharding.param_specs(params, self.mesh,
                                            fsdp_axes=fsdp_axes)
        o_specs = dist_sharding.opt_state_specs(
            jax.eval_shape(self.optimizer.init, params), p_specs, self.mesh,
            fsdp_axes=fsdp_axes)
        c_specs = dist_sharding.param_specs(consts, self.mesh,
                                            fsdp_axes=fsdp_axes)
        return p_specs, o_specs, c_specs

    def _mesh_ctx(self):
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def _place(self, state: TrainerState) -> TrainerState:
        """Place state on the mesh (no-op without one; a no-op copy for
        state :meth:`init_state` already placed). With ``sharding.fsdp``
        the train step is rebuilt to pin gradients to the sharded
        layout."""
        if self.mesh is None:
            return state
        from repro.dist import sharding as dist_sharding
        p_specs, o_specs, c_specs = self._specs(state.params, state.consts)
        if self.tc.sharding.fsdp:
            self._train_step = self._build_train_step(grad_specs=p_specs)
        return TrainerState(
            dist_sharding.place(state.params, self.mesh, p_specs),
            dist_sharding.place(state.opt_state, self.mesh, o_specs),
            dist_sharding.place(state.consts, self.mesh, c_specs),
            state.step)

    def save(self, state: TrainerState, background: Optional[bool] = None) -> None:
        bg = self.tc.async_ckpt if background is None else background
        self.ckpt.save(
            state.step,
            {"params": state.params, "opt_state": state.opt_state},
            config_hash=self.cfg.hash(),
            extra={"data": self.data.state_dict()},
            background=bg)

    def restore_or_init(self) -> TrainerState:
        state = self.init_state()
        if self.ckpt.latest_step() is None:
            return state
        try:
            with self.trace.span("resilience.restore", cat="resilience"):
                # step=None: checksum-verified, falls back newest → oldest
                # past corrupt checkpoints (ckpt/checkpoint.py)
                tree, manifest = self.ckpt.restore(
                    {"params": state.params, "opt_state": state.opt_state},
                    config_hash=self.cfg.hash())
        except CheckpointCorruptError as e:
            self.log(f"[trainer] every checkpoint failed verification "
                     f"({e}): starting fresh")
            return state
        self.data.restore(manifest["extra"]["data"])
        latest = int(manifest["step"])
        self.log(f"[trainer] resumed from step {latest}")
        return TrainerState(tree["params"], tree["opt_state"], state.consts,
                            step=latest)

    # -- resilience (module docstring: escalation policy) ---------------------
    def _next_valid_batch(self, step: int):
        """Next data batch, host-validated; corrupt batches (chaos or a
        real pipeline fault) are dropped and the cursor advances."""
        for _ in range(8):
            batch = self.data.next_batch()
            if self.chaos is not None:
                batch = self.chaos.corrupt_batch(step, batch)
            toks = batch["tokens"]
            if toks.dtype.kind in "iu" and \
                    bool(((toks >= 0) & (toks < self.cfg.vocab_size)).all()):
                return batch
            self._c_bad_batches.inc()
            self.log(f"[trainer] corrupt batch at step {step + 1}: "
                     "dropped, data cursor advanced")
        raise RuntimeError("data pipeline produced 8 consecutive corrupt "
                           "batches — not a transient fault, giving up")

    def _rollback(self, reason: str) -> TrainerState:
        """Divergence escalation: restore the newest intact checkpoint and
        skip the data cursor past the offending batches (skip doubles per
        rollback — the retry backoff). Bounded by ``max_rollbacks``."""
        self._rollbacks += 1
        self._c_rollbacks.inc()
        if self._rollbacks > self.max_rollbacks:
            raise RuntimeError(
                f"{reason} persisted through {self.max_rollbacks} "
                "rollbacks — giving up (raise --max-rollbacks or inspect "
                "the data/optimizer)")
        with self.trace.span("resilience.rollback", cat="resilience",
                             n=self._rollbacks):
            self.ckpt.wait()
            state = self.restore_or_init()
            skip = self.rollback_data_skip * (2 ** (self._rollbacks - 1))
            self.data.skip(skip)
        self._skip_streak = 0
        self.log(f"[trainer] rollback #{self._rollbacks} ({reason}): "
                 f"resumed step {state.step}, skipped {skip} data "
                 f"batch(es) forward")
        return self._place(state)

    # -- preemption -----------------------------------------------------------
    def _install_signal_handlers(self):
        def handler(signum, frame):
            self._preempted = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass  # not on main thread (tests)

    # -- loop -------------------------------------------------------------------
    def run(self, steps: Optional[int] = None,
            state: Optional[TrainerState] = None) -> TrainerState:
        tc = self.tc
        total = steps if steps is not None else tc.steps
        if state is None:
            state = self.restore_or_init()
        state = self._place(state)
        self._install_signal_handlers()
        while state.step < total:
            with self.trace.span("train.step", cat="train",
                                 step_num=state.step + 1):
                state = self._step(state, total)
        self.save(state, background=False)
        self.ckpt.wait()
        return state

    def _step(self, state: TrainerState, total: int) -> TrainerState:
        """One iteration of :meth:`run`: data, dispatch, sync, then the
        read-back of the step's metrics, logging, and the resilience and
        checkpoint decisions. Returns the state the loop goes on from."""
        tc = self.tc
        tokens_per_step = tc.global_batch * tc.seq_len
        if self.chaos is not None:
            # injected kills / checkpoint corruption (may raise
            # ChaosKill — a SystemExit(43) the relaunch recovers from)
            self.chaos.train_hook(state.step, ckpt_dir=self.tc.ckpt_dir)
        if self.fault_hook:
            self.fault_hook(state.step)  # test hook: may raise/kill
        t0 = time.perf_counter()
        with self.trace.span("train.data", cat="train"):
            batch_np = self._next_valid_batch(state.step)
            if self.chaos is not None and self.chaos.wants_poison:
                # constant pytree: the key rides along EVERY step
                # (value 1.0 off-fault), so chaos costs one compile
                batch_np = dict(batch_np)
                batch_np["chaos_scale"] = np.full(
                    (batch_np["tokens"].shape[0],),
                    self.chaos.poison_scale(state.step), np.float32)
            batch = {k: jax.numpy.asarray(v) for k, v in batch_np.items()}
        t1 = time.perf_counter()
        with self._mesh_ctx(), \
                self.trace.span("train.dispatch", cat="train"):
            params, opt_state, metrics = self._train_step(
                state.params, state.opt_state, state.consts, batch)
        t2 = time.perf_counter()
        if self.chaos is not None:
            self.chaos.straggle(state.step)  # inside the dt window
        with self.trace.span("train.sync", cat="train"):
            jax.block_until_ready(metrics["loss"])
        t3 = time.perf_counter()
        state = TrainerState(params, opt_state, state.consts, state.step + 1)
        if self._relora_merge is not None and \
                state.step % self.cfg.param.relora_period == 0:
            key = jax.random.fold_in(jax.random.PRNGKey(self.tc.seed),
                                     state.step)
            params, opt_state = self._relora_merge(
                state.params, state.opt_state, key)
            state = TrainerState(params, opt_state, state.consts, state.step)
            self.log(f"[trainer] ReLoRA merge+restart at {state.step}")
        with self.trace.span("train.readback", cat="train"):
            # dt keeps its historical meaning: dispatch + sync (excludes
            # host-side data work) — the watchdog/history currency
            dt = t3 - t1
            self._h_phase["data"].observe((t1 - t0) * 1e3)
            self._h_phase["dispatch"].observe((t2 - t1) * 1e3)
            self._h_phase["sync"].observe((t3 - t2) * 1e3)
            self._h_step.observe(dt * 1e3)
            self._c_steps.inc()
            self._c_tokens.inc(tokens_per_step)
            slow = self.watchdog.observe(state.step, dt)
            row = {k: float(v) for k, v in metrics.items()}
            row.update(step=state.step, dt=dt)
            self.metrics_history.append(row)
            skipped = row.get("nonfinite", 0.0) >= 1.0
            if skipped:
                # the jitted gate already kept the pre-step params/state;
                # here we only account and decide whether to escalate
                self._c_nonfinite.inc()
                self._skip_streak += 1
                self.log(f"[trainer] non-finite loss/grads at step "
                         f"{state.step}: update skipped "
                         f"({self._skip_streak}/{self.max_skips} before "
                         "rollback)")
            else:
                self._skip_streak = 0
            self._g_loss.set(row["loss"])
            if "lr" in row:
                self._g_lr.set(row["lr"])
            if "grad_norm" in row:
                self._g_gnorm.set(row["grad_norm"])
            self._g_tps.set(tokens_per_step / (t3 - t0))
            if state.step % tc.log_every == 0 or state.step == total:
                # log line reads back from the registry — the gauges ARE
                # the trainer's reporting surface, not a side channel
                self.log(f"[step {state.step:5d}] "
                         f"loss={self._g_loss.value:.4f} "
                         f"lr={self._g_lr.value or 0:.2e} {dt*1e3:.0f}ms "
                         f"{self._g_tps.value:.0f}tok/s"
                         + (" STRAGGLER" if slow else ""))
                if self.metrics_out:
                    self.obs.write_jsonl(self.metrics_out,
                                         extra={"step": state.step})
        if skipped and self._skip_streak >= self.max_skips:
            return self._rollback("non-finite loss/grads")
        if self._preempted:
            self.log("[trainer] preemption signal: checkpoint + exit 42")
            self.save(state, background=False)
            self.ckpt.wait()
            sys.exit(42)
        if tc.ckpt_every and state.step % tc.ckpt_every == 0:
            self.save(state)
        return state
