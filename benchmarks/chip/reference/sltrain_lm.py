"""Plain float32 reference of an SLTrain decoder LM and its AdamW step.

Straight ``jax.numpy`` from the published description, with no kernels,
caches or batching tricks, and no code of the program under test. Every
SLTrain weight is densified, W = (alpha/r)·B·A ⊕ V, and every matrix
product runs in float32 at ``Precision.HIGHEST`` (a TPU otherwise runs
float32 products in bfloat16 passes).

The block is the Qwen2 / Yi (LLaMA-style) one: RMSNorm before attention and
before the SwiGLU MLP, grouped-query causal attention with rotary position
embeddings (rotate-half convention, theta from the configuration), optional
bias on the q/k/v projections, an untied head. Parameters are stored in the
configuration's dtype between steps, as the configuration states; the
arithmetic of a step, the optimizer moments and the update are float32.

``precision="fp8"`` is the control: every matrix product takes its operands
through float8 e4m3 with a per-tensor scale (amax/448), the step below the
configuration's bfloat16. ``loss_tokens="first_half"`` is a planted fault:
the loss mean is taken over the first half of the positions only.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _dims(cfg):
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return (d, h, int(cfg["num_key_value_heads"]),
            int(cfg.get("head_dim") or d // h), int(cfg["num_hidden_layers"]))


def _fp8(x):
    """Per-tensor scaled float8 e4m3 rounding, passing gradients straight
    through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _ein(spec, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def dense_weight(B, A, v, cols, scale):
    """W = scale·B·A, plus v[i, j] at (i, cols[i, j])."""
    W = scale * jnp.matmul(B, A, precision=HIGHEST)
    rows = jnp.broadcast_to(jnp.arange(W.shape[0])[:, None], cols.shape)
    return W.at[rows, cols].add(v)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * inv[None]          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _linear(cfg, P, C, l, name, x, fp8):
    sl = cfg["sltrain"]
    B, A = P[f"{name}.B"][l], P[f"{name}.A"][l]
    W = dense_weight(B, A, P[f"{name}.v"][l], C[f"{name}.cols"][l],
                     float(sl["alpha"]) / B.shape[-1])
    y = _mm(x, W, fp8)
    if f"{name}.bias" in P:
        y = y + P[f"{name}.bias"][l]
    return y


def _layer(cfg, P, C, l, x, fp8):
    d, nh, nkv, hd, _ = _dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    bsz, s, _ = x.shape
    pos = jnp.arange(s)
    h = _rms(x, P["ln_attn"][l], eps)
    q = _linear(cfg, P, C, l, "wq", h, fp8).reshape(bsz, s, nh, hd)
    k = _linear(cfg, P, C, l, "wk", h, fp8).reshape(bsz, s, nkv, hd)
    v = _linear(cfg, P, C, l, "wv", h, fp8).reshape(bsz, s, nkv, hd)
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = nh // nkv
    qg = q.reshape(bsz, s, nkv, g, hd) * (hd ** -0.5)
    sc = _ein("bqhgd,bkhd->bhgqk", qg, k, fp8)
    causal = pos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _ein("bhgqk,bkhd->bqhgd", p, v, fp8).reshape(bsz, s, nh * hd)
    x = x + _linear(cfg, P, C, l, "wo", o, fp8)
    h = _rms(x, P["ln_mlp"][l], eps)
    a = _linear(cfg, P, C, l, "gate", h, fp8)
    u = _linear(cfg, P, C, l, "up", h, fp8)
    return x + _linear(cfg, P, C, l, "down", jax.nn.silu(a) * u, fp8)


def logits(cfg, P, C, tokens, precision="f32"):
    """(batch, seq) int tokens -> (batch, seq, vocab) float32 logits.
    ``P`` holds the float leaves (any float dtype), ``C`` the int ``cols``."""
    fp8 = precision == "fp8"
    P = {k: v.astype(F32) for k, v in P.items()}
    x = P["embed"][tokens]
    for l in range(_dims(cfg)[4]):
        x = jax.checkpoint(
            lambda x, P, l=l: _layer(cfg, P, C, l, x, fp8))(x, P)
    x = _rms(x, P["ln_f"], float(cfg["rms_norm_eps"]))
    head = P["embed"].T if cfg.get("tie_word_embeddings") else P["lm_head"]
    return _mm(x, head, fp8)


def loss(cfg, P, C, tokens, precision="f32", loss_tokens="all"):
    """Mean next-token cross-entropy over the sequence."""
    lg = logits(cfg, P, C, tokens, precision)[:, :-1]
    tgt = tokens[:, 1:]
    if loss_tokens == "first_half":
        n = tgt.shape[1] // 2
        lg, tgt = lg[:, :n], tgt[:, :n]
    lz = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0]
    return jnp.mean(lz - gold)


def lr_at(opt: dict, t):
    """Linear warmup over ``warmup_steps``, then cosine decay to
    ``min_lr_ratio`` of the peak at ``total_steps``; ``t`` counts updates
    from 1."""
    t = jnp.asarray(t, F32)
    w, total = float(opt["warmup_steps"]), float(opt["total_steps"])
    warm = jnp.minimum(1.0, t / max(w, 1.0))
    prog = jnp.clip((t - w) / max(total - w, 1.0), 0.0, 1.0)
    r = float(opt["min_lr_ratio"])
    return opt["lr"] * warm * (r + (1 - r) * 0.5 * (1 + jnp.cos(math.pi * prog)))


def _decayed(name: str) -> bool:
    """Weight decay applies to matrices, not to norms or biases."""
    return name in ("embed", "lm_head") or name.split(".")[-1] in ("B", "A",
                                                                   "v")


def make_step(cfg, opt, precision="f32", loss_tokens="all"):
    """One AdamW step: (params, mu, nu, t, tokens, cols) ->
    (params, mu, nu, loss). Global-norm clipping, bias-corrected moments,
    decoupled weight decay; params come back in their storage dtype."""
    b1, b2, eps = float(opt["beta1"]), float(opt["beta2"]), float(opt["eps"])
    wd, clip = float(opt["weight_decay"]), float(opt["grad_clip"])

    def step(P, mu, nu, t, tokens, C):
        Pf = {k: v.astype(F32) for k, v in P.items()}
        with jax.default_matmul_precision("highest"):
            lval, g = jax.value_and_grad(loss, argnums=1)(
                cfg, Pf, C, tokens, precision, loss_tokens)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        tf = jnp.asarray(t, F32)
        lr = lr_at(opt, t)
        newP, newmu, newnu = {}, {}, {}
        for k in P:
            gk = g[k] * scale
            m = b1 * mu[k] + (1 - b1) * gk
            v = b2 * nu[k] + (1 - b2) * gk * gk
            u = (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
            if wd > 0 and _decayed(k):
                u = u + wd * Pf[k]
            newP[k] = (Pf[k] - lr * u).astype(P[k].dtype)
            newmu[k], newnu[k] = m, v
        return newP, newmu, newnu, lval

    return jax.jit(step, donate_argnums=(0, 1, 2))


def _norms(tree, fn):
    return {k: float(v) for k, v in jax.jit(
        lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(fn(k, x))))
                   for k, x in t.items()})(tree).items()}


def train_readings(cfg, opt, make_weights, batches, precision="f32",
                   loss_tokens="all"):
    """Run len(batches) steps from ``make_weights()`` (canonical leaves,
    float ones in their storage dtype plus int ``*.cols``) and return the
    losses, each leaf's norm of the first gradient as the optimizer got it
    (mu / (1 - beta1) after step 1), and each leaf's norm of the change of
    its parameters over all the steps."""
    W = make_weights()
    C = {k: v for k, v in W.items() if k.endswith(".cols")}
    P = {k: v for k, v in W.items() if not k.endswith(".cols")}
    del W
    mu = {k: jnp.zeros(v.shape, F32) for k, v in P.items()}
    nu = {k: jnp.zeros(v.shape, F32) for k, v in P.items()}
    step = make_step(cfg, opt, precision, loss_tokens)
    b1 = float(opt["beta1"])
    losses, g1 = [], None
    for t, tok in enumerate(batches, 1):
        P, mu, nu, lval = step(P, mu, nu, t, jnp.asarray(tok), C)
        losses.append(float(lval))
        if t == 1:
            g1 = _norms(mu, lambda k, m: m / (1 - b1))
    del mu, nu
    P0 = {k: v for k, v in make_weights().items() if not k.endswith(".cols")}
    change = _norms({k: (P[k], P0[k]) for k in P},
                    lambda k, pq: pq[0].astype(F32) - pq[1].astype(F32))
    return {"losses": losses, "grad1": g1, "change": change}
