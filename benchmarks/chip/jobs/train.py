"""Training job kind: the program's ``Trainer`` and its jitted step, driven
from the seed, timed over a window, and checked against the plain f32
reference.

Set-up builds one ``Trainer`` and one state from the seeded weights, and
drives them through the first ``check_steps`` steps with the trainer's own
loop, fed by ``chipbench/tokens.ZipfDocs``, rows of one token-count profile
on every seed. Those steps compile the step program and
give the readings the reference is compared with: the first step's loss,
each leaf's norm of the first gradient as the optimizer got it (its first
moment over 1 - beta1 after one step), and each leaf's norm of the change
of its parameters over the steps. The window then goes on with the same trainer
and state, one ``Trainer.run`` call per step, until ``seconds`` have
passed; every step of the window is whole.

Traffic keys: ``seq_len``, ``batch``, ``exec_mode``, ``update_mode``,
``remat``, ``check_steps``, ``tokens`` (the parameters of the token source)
and ``optimizer`` (the program's ``OptimizerConfig`` fields).
"""
from __future__ import annotations

import gc
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec, tokens, weights, work

#: first-step gradients under this share of the median leaf's are nought
#: to rounding; such leaves move by round-off alone and are left out of
#: the change comparison
ZERO_GRAD_SHARE = 1e-3


def model_config(cfg: dict, traffic: dict):
    """The program's ModelConfig for a configuration file and traffic."""
    from repro.configs.base import ModelConfig, ParamConfig
    m = spec.dims(cfg)
    sl = cfg["sltrain"]
    return ModelConfig(
        name=cfg["name"], family=cfg["family"], n_layers=m["layers"],
        d_model=m["d"], n_heads=m["heads"], n_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab"],
        max_seq_len=int(cfg["max_position_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        qkv_bias=bool(cfg.get("attention_bias", False)),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=cfg["torch_dtype"],
        param=ParamConfig(mode="sltrain", rank=int(sl["rank"]),
                          delta=float(sl["delta"]), alpha=float(sl["alpha"]),
                          support_kind=sl["support"],
                          exec_mode=traffic["exec_mode"]))


def program_path(name: str) -> tuple:
    """Where a canonical leaf lives in the program's (params, consts)."""
    if name in ("embed", "lm_head", "ln_f"):
        return (name,)
    if name in ("ln_attn", "ln_mlp"):
        return ("layers", "k0", name)
    lin, part = name.split(".")
    group = "attn" if lin in ("wq", "wk", "wv", "wo") else "mlp"
    return ("layers", "k0", group, lin, part)


def _put(tree: dict, path: tuple, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree: dict, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


def program_params(pcfg, canon: dict) -> dict:
    """The program's param tree from canonical float leaves; the vocab
    axis is zero-padded to the program's padded vocabulary."""
    pad = pcfg.padded_vocab - pcfg.vocab_size
    out = {}
    for name, x in canon.items():
        if name == "embed":
            x = jnp.pad(x, ((0, pad), (0, 0)))
        elif name == "lm_head":
            x = jnp.pad(x, ((0, 0), (0, pad)))
        _put(out, program_path(name), x)
    return out


def program_consts(cfg: dict, cols: dict, tile_tables: bool) -> dict:
    """The program's const tree: each linear's ``cols`` plus, for the fused
    path, the tile tables the program makes from that support."""
    from repro.core import support
    from repro.kernels import ops
    out = {}
    sl = cfg["sltrain"]
    for lin in spec.linears(cfg):
        c = cols[f"{lin['name']}.cols"]
        entry = {"cols": c}
        if tile_tables:
            host = np.asarray(c)
            cap = support.tile_cap(lin["d_in"], lin["d_out"],
                                   float(sl["delta"]), sl["support"])
            rows = np.repeat(np.arange(lin["d_in"], dtype=np.int32), lin["k"])
            per_layer = [ops.prepare_tile_consts(rows, h.reshape(-1),
                                                 lin["d_in"], lin["d_out"],
                                                 pad=cap) for h in host]
            for key in per_layer[0]:
                entry[key] = jnp.stack([t[key] for t in per_layer])
        for key, x in entry.items():
            _put(out, program_path(f"{lin['name']}.{key}"), x)
    return out


def _check_tree(got: dict, want: dict, what: str) -> None:
    g = jax.tree_util.tree_flatten_with_path(got)[0]
    w = jax.tree_util.tree_flatten_with_path(want)[0]
    gs = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype)) for p, x in g}
    ws = {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype)) for p, x in w}
    if gs != ws:
        diff = sorted(set(gs.items()) ^ set(ws.items()))[:6]
        raise ValueError(f"{what} do not match the program's layout: {diff}")


def norms(picked: dict, fn) -> dict:
    """Name -> float32 norm of fn(value), in one jitted call."""
    out = jax.jit(lambda t: {n: jnp.sqrt(jnp.sum(jnp.square(
        fn(x).astype(jnp.float32)))) for n, x in t.items()})(picked)
    return {n: float(v) for n, v in out.items()}


def worst_leaf_gap(prog: dict, ref: dict, keep) -> tuple:
    """Largest |‖prog‖ − ‖ref‖| over max(‖ref‖, median ‖ref‖) among the
    kept leaves, and the leaf it was read on."""
    med = statistics.median(ref[n] for n in keep)
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med) for n in keep}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def compare(prog: dict, ref: dict) -> dict:
    """The numbers the limits hold: the relative gap of the first step's
    loss, and the worst leaf's gaps of the first gradient's and of the
    change's norms. Later steps' losses are given beside them, not held:
    at lr 3e-3 the loss leaves 10 for 13-18 by step 3, and its gap there
    is the chaos of that climb, not the program's precision."""
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                ref["losses"])]
    names = sorted(ref["grad1"])
    med = statistics.median(ref["grad1"][n] for n in names)
    moving = [n for n in names if ref["grad1"][n] >= ZERO_GRAD_SHARE * med]
    g, g_leaf = worst_leaf_gap(prog["grad1"], ref["grad1"], names)
    c, c_leaf = worst_leaf_gap(prog["change"], ref["change"], moving)
    return {"loss": gaps[0], "grad1": g, "change": c, "loss_steps": gaps,
            "grad1_leaf": g_leaf, "change_leaf": c_leaf,
            "left_out": sorted(set(names) - set(moving))}


class Job:
    def __init__(self, cell, seed: int):
        from repro.configs.base import (OptimizerConfig, ShardingConfig,
                                        TrainConfig)
        from repro.train.trainer import Trainer
        self.cell, self.seed = cell, int(seed)
        self.cfg, self.traffic = cell.config, cell.traffic
        t = self.traffic
        self.pcfg = model_config(self.cfg, t)
        self.ckpt_dir = tempfile.mkdtemp(prefix="chipbench_ckpt_")
        tc = TrainConfig(
            model=self.pcfg, optim=OptimizerConfig(**t["optimizer"]),
            sharding=ShardingConfig(update_mode=t["update_mode"],
                                    remat=t.get("remat", "none")),
            seed=self.seed, global_batch=int(t["batch"]),
            seq_len=int(t["seq_len"]), steps=1, log_every=1 << 30,
            ckpt_every=0, ckpt_dir=self.ckpt_dir, async_ckpt=False)
        self.tc = tc
        self.trainer = Trainer(tc, log_fn=lambda *_: None)
        self.trainer.data = self._feed()
        # the window measures training steps; checkpoint writes (a whole
        # state to disk at the end of every Trainer.run) are not part of it
        self.trainer.save = lambda *a, **k: None
        self.tokens_per_step = tc.global_batch * tc.seq_len
        self.state = None
        self.readings = None
        self.window_steps = 0

    # -- set-up ---------------------------------------------------------------
    def float_names(self):
        return sorted(n for n in weights.shapes(self.cfg)
                      if not n.endswith(".cols"))

    def _program_params(self):
        canon = weights.generate(self.cfg, self.seed, self.float_names())
        return program_params(self.pcfg, canon)

    def setup(self) -> None:
        from repro.models import lm
        from repro.train.trainer import TrainerState
        canon = weights.generate(self.cfg, self.seed)
        params = program_params(self.pcfg, {k: v for k, v in canon.items()
                                            if not k.endswith(".cols")})
        consts = program_consts(self.cfg, {k: v for k, v in canon.items()
                                           if k.endswith(".cols")},
                                self.traffic["exec_mode"] == "fused")
        del canon
        want_p, want_c = lm.init_lm(self.pcfg, key=None)
        _check_tree(params, want_p, "seeded params")
        _check_tree(consts, want_c, "tile consts")
        opt = self.trainer.optimizer.init(params)
        state = TrainerState(params, opt, consts, 0)
        names = self.float_names()
        b1 = float(self.traffic["optimizer"]["beta1"])
        grad1 = None
        for _ in range(int(self.traffic["check_steps"])):
            state = self.trainer.run(steps=state.step + 1, state=state)
            if grad1 is None:
                mu = state.opt_state["mu"]
                grad1 = norms({n: _get(mu, program_path(n)) for n in names},
                              lambda m: m / (1 - b1))
        p0 = self._program_params()
        change = norms({n: (_get(state.params, program_path(n)),
                            _get(p0, program_path(n))) for n in names},
                       lambda ab: ab[0].astype(jnp.float32) - ab[1])
        del p0
        losses = [h["loss"] for h in self.trainer.metrics_history]
        self.readings = {"losses": losses, "grad1": grad1, "change": change}
        self.state = state

    # -- window ---------------------------------------------------------------
    def _data_hist(self):
        return self.trainer.obs.histogram("train.phase_ms").labels(
            phase="data")

    def window(self, seconds: float) -> None:
        h = self._data_hist()
        n0, s0 = h.count, h.sum
        hist0 = len(self.trainer.metrics_history)
        state = self.state
        t0 = time.perf_counter()
        while True:
            state = self.trainer.run(steps=state.step + 1, state=state)
            self.window_steps += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        self.state = state
        rows = self.trainer.metrics_history[hist0:]
        self.failed = sum(r.get("nonfinite", 0.0) >= 1.0 for r in rows)
        n = h.count - n0
        self.data_ms = (h.sum - s0) / n if n else None

    def end_to_end(self, peak_bytes: int) -> dict:
        return {"train_tokens_per_s": self.window_steps
                * self.tokens_per_step / self.window_s,
                "train_peak_hbm_gib": peak_bytes / float(1 << 30)}

    def counts(self) -> tuple:
        return self.window_steps, self.failed

    def step_temp_bytes(self) -> int:
        """Temporary bytes of the compiled step program, by the compiler."""
        sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        s = self.state
        batch = {"tokens": jax.ShapeDtypeStruct(
            (self.tc.global_batch, self.tc.seq_len), jnp.int32)}
        lowered = self.trainer._train_step.lower(
            jax.tree.map(sds, s.params), jax.tree.map(sds, s.opt_state),
            jax.tree.map(sds, s.consts), batch)
        return int(lowered.compile().memory_analysis().temp_size_in_bytes)

    def layer_context(self) -> dict:
        """What the per-layer readers of a traced run read."""
        m = self.tokens_per_step
        return {"job": "train", "steps": self.window_steps,
                "tokens": self.window_steps * m,
                "flops_per_token": work.train_flops_per_token(
                    self.cfg, self.tc.seq_len),
                "sl_calls_per_step": work.train_sl_calls(self.cfg, m),
                "data_ms": self.data_ms,
                "step_temp_bytes": self.step_temp_bytes()}

    # -- check ----------------------------------------------------------------
    def free(self) -> None:
        """Drop the program's state and trainer before the reference runs."""
        self.state = None
        self.trainer = None
        gc.collect()
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)

    def _feed(self) -> tokens.ZipfDocs:
        return tokens.ZipfDocs(self.pcfg.vocab_size, self.tc.seq_len,
                                 self.tc.global_batch, self.seed,
                                 **self.traffic["tokens"])

    def batches(self) -> list:
        """The token rows of the checked steps: the feed's first blocks."""
        feed = self._feed()
        return [feed.next_batch()["tokens"]
                for _ in range(int(self.traffic["check_steps"]))]

    def reference(self, precision="f32", loss_tokens="all") -> dict:
        from chipbench.harness import load_module
        ref = load_module(self.cell.path("reference", "sltrain_lm.py"),
                          "chipbench_reference_sltrain_lm")
        return ref.train_readings(
            self.cfg, self.traffic["optimizer"],
            lambda: weights.generate(self.cfg, self.seed), self.batches(),
            precision=precision, loss_tokens=loss_tokens)

    def check(self, limits: dict) -> tuple:
        numbers = compare(self.readings, self.reference())
        checks = {k: {"value": numbers[k], "limit": limits[k]}
                  for k in ("loss", "grad1", "change")}
        correct = all(v["value"] <= v["limit"] for v in checks.values())
        return correct, checks, numbers
