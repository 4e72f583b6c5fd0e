"""Seeded weights of an SLTrain decoder LM, made on the device in one jitted
call, in the type they are trained and served in.

The same seed gives the same weights, so the program under test and the
plain reference start from one set of numbers without either handing the
other anything it made. Names are canonical (``embed``, ``lm_head``,
``ln_f``, ``ln_attn``, ``ln_mlp`` and ``<linear>.<B|A|v|cols|bias>``); the
per-layer leaves carry a leading layer axis.

Distributions, chosen so that every leaf has a live gradient at step 1:

* embedding and head: normal, std 1/sqrt(hidden);
* norms: ones; biases: normal, std 0.02;
* ``A``: uniform ±sqrt(6/d_in) and ``v``: uniform ±1/sqrt(d_in), as the
  SLTrain paper initialises them;
* ``B``: normal with std sqrt(delta·r/6)/alpha, which puts the low-rank
  branch at the sparse branch's scale (the paper starts B at zero, which
  leaves dA at exactly zero on the first step);
* the support: row-balanced, round(delta·d_out) columns per row, one drawn
  uniformly from each of that many equal column strata, so each row's
  columns are distinct and sorted. The program draws a uniform k-subset
  per row; the strata fill every 128×128 tile alike (PERF.md, Open
  questions).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec


def base_key(seed: int):
    """A PRNG key for any non-negative seed: PRNGKey keeps only the low 32
    bits, so the high bits are folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def shapes(cfg: dict) -> dict:
    """Canonical name -> (shape, dtype name)."""
    m = spec.dims(cfg)
    dt = cfg.get("torch_dtype", "bfloat16")
    L, d, V = m["layers"], m["d"], m["vocab"]
    out = {"embed": ((V, d), dt), "ln_f": ((d,), dt),
           "ln_attn": ((L, d), dt), "ln_mlp": ((L, d), dt)}
    if not cfg.get("tie_word_embeddings"):
        out["lm_head"] = ((d, V), dt)
    for lin in spec.linears(cfg):
        n, di, do, r, k = (lin[x] for x in ("name", "d_in", "d_out", "rank",
                                             "k"))
        out[f"{n}.B"] = ((L, di, r), dt)
        out[f"{n}.A"] = ((L, r, do), dt)
        out[f"{n}.v"] = ((L, di, k), dt)
        out[f"{n}.cols"] = ((L, di, k), "int32")
        if lin["bias"]:
            out[f"{n}.bias"] = ((L, do), dt)
    return out


def _leaf(cfg: dict, name: str, shape, dtype, key):
    m = spec.dims(cfg)
    sl = cfg["sltrain"]
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    normal = lambda std: jax.random.normal(k, shape, jnp.float32) * std
    uniform = lambda lim: jax.random.uniform(k, shape, jnp.float32, -lim, lim)
    if name in ("embed", "lm_head"):
        x = normal(1.0 / np.sqrt(m["d"]))
    elif name in ("ln_f", "ln_attn", "ln_mlp"):
        x = jnp.ones(shape, jnp.float32)
    else:
        lin_name, part = name.split(".")
        lin = next(l for l in spec.linears(cfg) if l["name"] == lin_name)
        if part == "bias":
            x = normal(0.02)
        elif part == "A":
            x = uniform(np.sqrt(6.0 / lin["d_in"]))
        elif part == "v":
            x = uniform(1.0 / np.sqrt(lin["d_in"]))
        elif part == "B":
            x = normal(np.sqrt(float(sl["delta"]) * lin["rank"] / 6.0)
                       / float(sl["alpha"]))
        else:  # cols
            b = np.asarray(spec.strata(lin["d_out"], lin["k"]), np.int32)
            lo, width = jnp.asarray(b[:-1]), jnp.asarray(np.diff(b))
            u = jax.random.uniform(k, shape, jnp.float32)
            off = jnp.minimum((u * width).astype(jnp.int32), width - 1)
            return (lo + off).astype(jnp.int32)
    return x.astype(dtype)


def generate(cfg: dict, seed: int, names=None) -> dict:
    """All canonical leaves (or ``names`` only) for ``seed``, on the
    default device, from one jitted call."""
    sh = shapes(cfg)
    names = tuple(sorted(sh if names is None else names))

    def make(key):
        return {n: _leaf(cfg, n, sh[n][0], jnp.dtype(sh[n][1]), key)
                for n in names}

    return jax.jit(make)(base_key(seed))
