"""Shared model building blocks + the parameter Builder.

The Builder abstracts "concrete init" (real arrays, numpy support sampling)
vs "abstract init" (ShapeDtypeStruct, zero allocation) so every model's
parameter structure is written exactly once and the dry-run can build 405B
models on a laptop (DESIGN §6).
"""
from __future__ import annotations

import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ParamConfig
from repro.core import lowrank, relora, sltrain


def _name_hash(path: str) -> int:
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


class Builder:
    """Creates parameter/const pytrees; concrete iff key is not None."""

    def __init__(self, cfg: ModelConfig, key=None, path: str = "", seed: int = 0):
        self.cfg = cfg
        self.key = key
        self.path = path
        self.seed = seed
        self.dtype = jnp.dtype(cfg.dtype)

    @property
    def concrete(self) -> bool:
        return self.key is not None

    def sub(self, name: str) -> "Builder":
        k = None
        if self.key is not None:
            k = jax.random.fold_in(self.key, _name_hash(name))
        return Builder(self.cfg, k, f"{self.path}/{name}", self.seed)

    # -- raw tensors --------------------------------------------------------
    def tensor(self, name: str, shape: Tuple[int, ...], init: str = "normal",
               fan_in: Optional[int] = None, dtype=None):
        dtype = dtype or self.dtype
        if not self.concrete:
            return jax.ShapeDtypeStruct(shape, dtype)
        k = jax.random.fold_in(self.key, _name_hash(name))
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        fan = fan_in if fan_in is not None else (shape[0] if len(shape) >= 2 else shape[-1])
        if init == "normal":
            std = 1.0 / np.sqrt(fan)
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        if init == "kaiming":
            lim = np.sqrt(6.0 / fan)
            return jax.random.uniform(k, shape, jnp.float32, -lim, lim).astype(dtype)
        raise ValueError(init)

    # -- linear factory (the paper's technique plugs in here) ---------------
    def linear(self, name: str, d_in: int, d_out: int, adapted: bool = True,
               bias: bool = False):
        """Returns (params, consts). ``adapted=False`` forces dense (embeds,
        routers, norms-adjacent projections the paper keeps full-rank)."""
        pc: ParamConfig = self.cfg.param
        b = self.sub(name)
        consts: dict = {}
        # per-matrix effective rank: global rank capped at half the min dim
        # (MoE expert / gate matrices are much smaller than attention ones)
        r = max(4, min(pc.rank, min(d_in, d_out) // 2))
        if (not adapted) or pc.mode == "dense":
            params = {"w": b.tensor("w", (d_in, d_out), "normal", fan_in=d_in)}
        elif pc.mode == "lowrank":
            if b.concrete:
                params = lowrank.init_params(b.key, d_in, d_out, r, b.dtype)
            else:
                params = lowrank.abstract_params(d_in, d_out, r, b.dtype)
        elif pc.mode == "relora":
            if b.concrete:
                params = relora.init_params(b.key, d_in, d_out, r, b.dtype)
            else:
                params = relora.abstract_params(d_in, d_out, r, b.dtype)
        elif pc.mode == "sltrain":
            # exec_mode="fused" adds the tile-CSR index consts; their
            # shapes are deterministic (support.tile_cap), so the abstract
            # twin matches and stack_layers can stack them across layers
            if b.concrete:
                params, consts = sltrain.init_params(
                    b.key, d_in, d_out, r, pc.delta, b.dtype,
                    pc.support_kind, seed=self.seed ^ _name_hash(b.path),
                    exec_mode=pc.exec_mode)
            else:
                params, consts = sltrain.abstract_params(
                    d_in, d_out, r, pc.delta, b.dtype, pc.support_kind,
                    exec_mode=pc.exec_mode)
        else:
            raise ValueError(pc.mode)
        if bias:
            params["bias"] = b.tensor("bias", (d_out,), "zeros")
        return params, consts


def apply_linear(cfg: ModelConfig, params, consts, x, adapted: bool = True):
    pc = cfg.param
    if (not adapted) or pc.mode == "dense" or "w" in params:
        y = x @ params["w"]
    else:
        # per-matrix scale alpha/r_eff (r_eff capped at init, see Builder.linear)
        scale = pc.alpha / params["B"].shape[-1]
        if pc.mode == "lowrank":
            y = lowrank.lr_matmul(x, params, scale)
        elif pc.mode == "relora":
            y = relora.rl_matmul(x, params, scale)
        elif pc.mode == "sltrain":
            y = sltrain.sl_matmul(x, params, consts, scale, pc.exec_mode)
        else:
            raise ValueError(pc.mode)
    if "bias" in params:
        y = y + params["bias"].astype(y.dtype)
    return y


def scoped_linear(cfg: ModelConfig, params, consts, name: str, x):
    """:func:`apply_linear` of the linear ``params[name]`` under a
    ``jax.named_scope`` of its name, so its device ops carry it."""
    with jax.named_scope(name):
        return apply_linear(cfg, params[name], consts.get(name, {}), x)


# ---------------------------------------------------------------------------
# Normalization / activations / rope
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Ambient-mesh sharding constraints (§Perf: SP / attention layouts)
#
# Owned by repro.dist.sharding; re-exported here because every model file
# already imports them from common.
# ---------------------------------------------------------------------------

from repro.dist.sharding import ambient_mesh, constrain  # noqa: E402,F401


def rms_norm(x, weight, eps: float = 1e-6, plus_one: bool = False):
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if plus_one:                      # gemma convention: scale = (1 + w)
        w = 1.0 + w
    return (n * w).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    n = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (n * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def softcap(x, cap: float):
    if cap <= 0:
        return x
    return jnp.tanh(x / cap) * cap


def rope(x, pos, theta: float = 10000.0):
    """Rotary embedding. x: (..., seq, heads, head_dim); pos: (..., seq)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (np.log(theta) / half))
    ang = pos[..., :, None].astype(jnp.float32) * freqs[None, :]   # (..., s, half)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# Abstract stacking helper
# ---------------------------------------------------------------------------

def remat_wrap(fn, remat: str):
    """Apply the configured remat policy ("none" | "full" |
    "dots_saveable") to a scan-step/segment function. Single owner of the
    policy-name mapping: the train forward (lm.apply_lm), the
    boundary-saving forward and the per-layer backward sweep
    (train/perlayer.py) must recompute under the SAME policy."""
    if remat == "none":
        return fn
    policy = None if remat == "full" else \
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint(fn, policy=policy)


def stack_layers(builder: Builder, fn, n: int, name: str = "layer"):
    """Stack per-layer (params, consts) along a new leading axis.

    Concrete: calls fn once per layer (distinct keys/supports) and stacks.
    Abstract: calls fn once and prepends n to every leaf shape (O(1))."""
    if n == 0:
        return {}, {}
    if builder.concrete:
        ps, cs = zip(*(fn(builder.sub(f"{name}{i}")) for i in range(n)))
        stackf = lambda *xs: jnp.stack(xs)
        params = jax.tree.map(stackf, *ps) if ps[0] else {}
        consts = jax.tree.map(stackf, *cs) if cs[0] else {}
        return params, consts
    p, c = fn(builder.sub(f"{name}0"))
    add = lambda t: jax.ShapeDtypeStruct((n,) + tuple(t.shape), t.dtype)
    return jax.tree.map(add, p), jax.tree.map(add, c)
