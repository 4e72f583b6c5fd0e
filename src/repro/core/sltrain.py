"""SLTrain linear layer: W = (alpha/r) * B @ A  ⊕_I  V   (paper §3.2, Alg. 1).

Two support layouts:

* ``row_balanced`` (default) — each row holds exactly k = round(δ·d_out)
  entries; stored as 2-D ``cols (d_in, k)`` / ``v (d_in, k)`` with the row
  indices IMPLICIT (iota). Halves index memory vs COO (and is 4x smaller
  than the paper's int64 convention), shards naturally along d_in, and
  makes ∇V a single take_along_axis gather. TPU adaptation, DESIGN §3.
* ``iid`` — the paper's uniform sampling, flat COO (rows, cols, v).

Four execution modes (DESIGN §3; the full matrix lives in
``configs.base.ParamConfig``):

* ``dense``  — densify-on-the-fly then one MXU matmul; custom VJP implements
  the paper's eq. (2): dense W is recomputed, never stored as a residual.
* ``sparse`` — beyond-paper factored path for decode: reads only the
  factored bytes from HBM (the decode memory-roofline win).
* ``fused``  — Pallas path for training: sl_matmul densifies each 128×128
  tile in VMEM and feeds it straight to the MXU (forward + dx), sddmm
  gathers dV without the G transient (backward) — the dense W never
  touches HBM at all. Requires tile consts from init
  (``init_params(..., exec_mode="fused")``): int32 {rows_t, cols_t, perm}
  with a DETERMINISTIC per-tile capacity (``support.tile_cap``) so the
  no-alloc dry-run twin and per-layer stacking agree; the trainable ``v``
  stays flat and is gathered/scattered through ``perm`` inside the jit.
* ``quant`` — serve-only post-training path (repro.quant): the sparse
  values run as int8 tile-CSR codes against per-output-channel f32
  scales through the quantized Pallas decode kernel; B/A stay bf16 with
  the quantization error SVD-folded in (SLiM-style). Requires the
  calibrated consts {qv_t, rows_q, cols_q, qscale} from
  ``quant.calibrate``; training rejects this mode (train/step.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import support as support_lib


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

# Seed stride for the host-side re-sample fallback when a sampled support
# exceeds the deterministic tile_cap bound (astronomically rare; see
# support.tile_cap). Deterministic so elastic restore re-derives the same
# final support.
_RESAMPLE_STRIDE = 0x9E3779B1
_RESAMPLE_ATTEMPTS = 16


def prepare_fused_consts(rows, cols, d_in: int, d_out: int, delta: float,
                         support_kind: str, seed: int):
    """Tile consts {rows_t, cols_t, perm} for ``exec_mode="fused"`` at the
    deterministic ``support.tile_cap`` capacity. Returns
    (rows, cols, consts): if the sampled support busts the bound the
    support is re-sampled on host with a deterministically bumped seed and
    the (possibly new) COO arrays are returned alongside the consts."""
    from repro.kernels import ops
    cap = support_lib.tile_cap(d_in, d_out, delta, support_kind)
    for attempt in range(_RESAMPLE_ATTEMPTS):
        try:
            tiles = ops.prepare_tile_consts(rows, cols, d_in, d_out, pad=cap)
            return rows, cols, tiles
        except ValueError:
            rows, cols = support_lib.sample_support(
                seed + (attempt + 1) * _RESAMPLE_STRIDE, d_in, d_out, delta,
                support_kind)
    raise ValueError(
        f"fused tile capacity {cap} too small for ({d_in}, {d_out}, "
        f"delta={delta}, {support_kind}) after {_RESAMPLE_ATTEMPTS} "
        "re-samples — support.tile_cap bound is broken for this shape")


def init_params(key, d_in: int, d_out: int, rank: int, delta: float,
                dtype=jnp.bfloat16, support_kind: str = "row_balanced",
                seed: int = 0, exec_mode: str = "dense"):
    """Init (params, consts). LoRA-style init (paper §3.3): Kaiming-uniform
    A, zero B, v ~ U[-1/sqrt(d_in), 1/sqrt(d_in)].

    ``exec_mode="fused"`` additionally emits the int32 tile consts
    {rows_t, cols_t, perm} the Pallas custom-VJP linear consumes, padded to
    the deterministic ``support.tile_cap`` capacity (abstract dry-run and
    per-layer stacking both rely on shape determinism). The trainable
    params are IDENTICAL across exec modes — same sampled support, same
    flat ``v`` — so checkpoints and optimizer state are layout-independent
    and a dense-mode run with the same seed is token-for-token comparable."""
    k_a, k_v = jax.random.split(key)
    lim_a = float(np.sqrt(6.0 / d_in))
    lim_v = float(1.0 / np.sqrt(d_in))
    rows, cols = support_lib.sample_support(seed, d_in, d_out, delta, support_kind)
    tiles = None
    if exec_mode == "fused":
        rows, cols, tiles = prepare_fused_consts(
            rows, cols, d_in, d_out, delta, support_kind, seed)
    if support_kind == "row_balanced":
        k = cols.shape[0] // d_in
        v_shape = (d_in, k)
        consts = {"cols": jnp.asarray(cols.reshape(d_in, k))}
    else:
        v_shape = (cols.shape[0],)
        consts = {"rows": jnp.asarray(rows), "cols": jnp.asarray(cols)}
    if tiles is not None:
        consts.update(tiles)
    params = {
        "B": jnp.zeros((d_in, rank), dtype=dtype),
        "A": jax.random.uniform(k_a, (rank, d_out), dtype=jnp.float32,
                                minval=-lim_a, maxval=lim_a).astype(dtype),
        "v": jax.random.uniform(k_v, v_shape, dtype=jnp.float32,
                                minval=-lim_v, maxval=lim_v).astype(dtype),
    }
    return params, consts


def abstract_params(d_in: int, d_out: int, rank: int, delta: float,
                    dtype=jnp.bfloat16, support_kind: str = "row_balanced",
                    exec_mode: str = "dense"):
    """ShapeDtypeStruct twin of ``init_params`` for the no-alloc dry-run.
    With ``exec_mode="fused"`` the tile-const shapes are exact (not a
    bound-by-coincidence): concrete init pads every tile to the same
    deterministic ``support.tile_cap`` capacity this computes."""
    nnz = support_lib.nnz_for(d_in, d_out, delta, support_kind)
    sds = jax.ShapeDtypeStruct
    params = {"B": sds((d_in, rank), dtype), "A": sds((rank, d_out), dtype)}
    if support_kind == "row_balanced":
        k = nnz // d_in
        params["v"] = sds((d_in, k), dtype)
        consts = {"cols": sds((d_in, k), jnp.int32)}
    else:
        params["v"] = sds((nnz,), dtype)
        consts = {"rows": sds((nnz,), jnp.int32), "cols": sds((nnz,), jnp.int32)}
    if exec_mode == "fused":
        tile = support_lib.TILE
        nkt = (d_in + tile - 1) // tile
        nnt = (d_out + tile - 1) // tile
        cap = support_lib.tile_cap(d_in, d_out, delta, support_kind)
        for name in ("rows_t", "cols_t", "perm"):
            consts[name] = sds((nkt, nnt, cap), jnp.int32)
    return params, consts


# ---------------------------------------------------------------------------
# Densify
# ---------------------------------------------------------------------------

def _lowrank_dense(B, A, scale):
    return (scale * (B.astype(jnp.float32) @ A.astype(jnp.float32))).astype(B.dtype)


def densify_rb(B, A, v, cols, scale: float):
    """Row-balanced densify: batched per-row scatter at implicit rows."""
    W = _lowrank_dense(B, A, scale)
    d_in = W.shape[0]
    rows = jnp.broadcast_to(jnp.arange(d_in, dtype=jnp.int32)[:, None], cols.shape)
    return W.at[rows, cols].add(v.astype(W.dtype), mode="drop",
                                unique_indices=True)


def densify_coo(B, A, v, rows, cols, scale: float):
    W = _lowrank_dense(B, A, scale)
    return W.at[rows, cols].add(v.astype(W.dtype), mode="drop",
                                unique_indices=True)


# ---------------------------------------------------------------------------
# Dense-mode matmul, row-balanced layout (paper eq. 2 backward)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _sl_matmul_rb(x, B, A, v, cols, scale):
    return x @ densify_rb(B, A, v, cols, scale)


def _sl_matmul_rb_fwd(x, B, A, v, cols, scale):
    # Residuals: factored params + input ONLY (Alg. 1 save_for_backward).
    return x @ densify_rb(B, A, v, cols, scale), (x, B, A, v, cols)


def _grads_from_G_local(xf, dyf, A, B, v, cols, scale):
    """(dB, dA, dv) from a device-local G transient (paper eq. 2).

    G accumulates in f32 (preferred_element_type, NOT a bf16 matmul whose
    result is cast after — that rounds the whole token contraction through
    bf16 first, the PR-1 sparse-decode bug class) so the densify path
    agrees with the fused sddmm kernel, which accumulates its G tiles in
    f32 the same way."""
    G = jnp.matmul(xf.T, dyf, preferred_element_type=jnp.float32)
    dB = (scale * (G @ A.astype(jnp.float32).T)).astype(B.dtype)
    dA = (scale * (B.astype(jnp.float32).T @ G)).astype(A.dtype)
    dv = jnp.take_along_axis(G, cols.astype(jnp.int32), axis=1
                             ).astype(v.dtype)
    return dB, dA, dv


def _grads_distributed(x, dy, A, B, v, cols, scale):
    """Distributed eq. (2) (§Perf it.6/it.8, DESIGN §4).

    Under pjit-auto the token contraction G = xᵀ·dy spans every device, so
    XLA all-reduces the full d_in×d_out f32 transient BEFORE the factor
    projections / support gather — ~0.6 GB of wire per matrix per layer,
    the dominant collective of the whole train step. The token-sum commutes
    with all three consumers of G, so under shard_map we form only a LOCAL
    G slice and psum the r- and k-sized RESULTS instead:
        wire: d_in·d_out·4  →  (d_in+d_out)·r·4 + nnz·4   (~20-30× less).

    Layout (it.8): tokens sharded over (pod, data); d_out sharded over
    "model" — the SAME gather-x + TP-output layout the forward uses, so the
    island does not flip the surrounding rematted matmuls into redundant
    gather-W form (the it.6 lesson: a seq-sharded island de-sharded the
    whole backward region, 5× compute). Each device computes the
    (d_in × d_out/TP) G slice it would have computed as a partial anyway."""
    from repro.dist import sharding as dist_sharding
    mesh = dist_sharding.ambient_mesh()
    if mesh is None or getattr(mesh, "empty", False) or x.ndim < 3:
        return None
    if x.shape[-1] > dy.shape[-1]:
        # island edge would gather the LARGER activation (e.g. the d_ff
        # hidden of a down-projection) — the gather costs more wire than
        # the G all-reduce it avoids (§Perf it.9 napkin math); use the
        # local-G pjit path instead.
        return None
    axes = mesh.axis_names
    bt = tuple(a for a in ("pod", "data") if a in axes)
    import numpy as _np
    nb = int(_np.prod([mesh.shape[a] for a in bt])) if bt else 1
    nm = mesh.shape.get("model", 1) if "model" in axes else 1
    d_in = x.shape[-1]
    d_out = dy.shape[-1]
    r = A.shape[0]
    if not bt or x.shape[0] % nb or d_out % nm or nm <= 1:
        return None
    d_out_loc = d_out // nm
    from jax.sharding import PartitionSpec as P

    def body(xs, dys, A_l, B_r, cols_r):
        xl = xs.reshape(-1, d_in)                       # (Mloc, d_in)
        dyl = dys.reshape(-1, d_out_loc)                # (Mloc, d_out/TP)
        Gl = (xl.T @ dyl).astype(jnp.float32)           # local G slice
        dBl = scale * (Gl @ A_l.astype(jnp.float32).T)  # partial over model
        dAl = scale * (B_r.astype(jnp.float32).T @ Gl)  # partial over bt
        # support gather restricted to this rank's d_out columns
        base = jax.lax.axis_index("model") * d_out_loc
        cl = cols_r.astype(jnp.int32) - base
        ok = (cl >= 0) & (cl < d_out_loc)
        dvl = jnp.take_along_axis(Gl, jnp.clip(cl, 0, d_out_loc - 1), axis=1)
        dvl = jnp.where(ok, dvl, 0.0)
        dB = jax.lax.psum(dBl, bt + ("model",))
        dA = jax.lax.psum(dAl, bt)
        dv = jax.lax.psum(dvl, bt + ("model",))
        return dB, dA, dv

    try:
        dB, dA, dv = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(bt, None, None), P(bt, None, "model"),
                      P(None, "model"), P(None, None), P(None, None)),
            out_specs=(P(None, None), P(None, "model"), P(None, None)),
            check_vma=False)(x, dy, A, B, cols)
        return dB.astype(B.dtype), dA.astype(A.dtype), dv.astype(v.dtype)
    except Exception:
        return None


def _sl_matmul_rb_bwd(scale, res, dy):
    x, B, A, v, cols = res
    d_in = x.shape[-1]
    d_out = dy.shape[-1]
    # Backward activations in the model dtype (§Perf it.9): upstream ops
    # (norm/softmax backward) hand us f32 cotangents; every collective the
    # partitioner inserts on dy/dx pays 2× for it. bf16 grads are standard.
    dy = dy.astype(x.dtype)
    xf = x.reshape(-1, d_in)
    dyf = dy.reshape(-1, d_out)
    # Distributed eq. (2) when a mesh is ambient (§Perf it.6); else the
    # paper's local-G path. Either way G is a transient, never a residual.
    out = _grads_distributed(x, dy, A, B, v, cols, scale)
    if out is None:
        out = _grads_from_G_local(xf, dyf, A, B, v, cols, scale)
    dB, dA, dv = out
    # dx needs W^T: recompute the densified W (the paper's explicit trade:
    # "we never store it").
    W = densify_rb(B, A, v, cols, scale)
    dx = (dyf @ W.T).reshape(x.shape).astype(x.dtype)
    # NOTE §Perf it.11 (REFUTED): pinning dx seq-sharded here to force a
    # reduce-scatter measured t_x 40.9 -> 43.0 s — the pin creates extra
    # reshards in the surrounding remat region. Left unpinned.
    return dx, dB, dA, dv, None


_sl_matmul_rb.defvjp(_sl_matmul_rb_fwd, _sl_matmul_rb_bwd)


# ---------------------------------------------------------------------------
# Dense-mode matmul, COO layout (paper-faithful iid support)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _sl_matmul_coo(x, B, A, v, support, scale):
    rows, cols = support
    return x @ densify_coo(B, A, v, rows, cols, scale)


def _sl_matmul_coo_fwd(x, B, A, v, support, scale):
    rows, cols = support
    return x @ densify_coo(B, A, v, rows, cols, scale), (x, B, A, v, rows, cols)


def _sl_matmul_coo_bwd(scale, res, dy):
    x, B, A, v, rows, cols = res
    d_in = x.shape[-1]
    d_out = dy.shape[-1]
    xf = x.reshape(-1, d_in)
    dyf = dy.reshape(-1, d_out)
    # f32 accumulation via preferred_element_type (same contract as the
    # row-balanced path's _grads_from_G_local)
    G = jnp.matmul(xf.T, dyf, preferred_element_type=jnp.float32)
    dB = (scale * (G @ A.astype(jnp.float32).T)).astype(B.dtype)
    dA = (scale * (B.astype(jnp.float32).T @ G)).astype(A.dtype)
    dv = G[rows, cols].astype(v.dtype)
    W = densify_coo(B, A, v, rows, cols, scale)
    dx = (dyf @ W.T).reshape(x.shape).astype(x.dtype)
    return dx, dB, dA, dv, None


_sl_matmul_coo.defvjp(_sl_matmul_coo_fwd, _sl_matmul_coo_bwd)


# ---------------------------------------------------------------------------
# Sparse-mode (factored) matmul — decode path
# ---------------------------------------------------------------------------

def _sl_matmul_sparse(x, B, A, v, rows, cols, scale, chunk: int = 1 << 20):
    """y = scale·(x@B)@A + sparse term, without densifying W. Reads only
    O((d_in+d_out)·r + nnz) parameter bytes — decode is memory-bound, so the
    compression ratio becomes decode bandwidth (DESIGN §3)."""
    lead = x.shape[:-1]
    d_in = x.shape[-1]
    d_out = A.shape[-1]
    # Accumulate in f32 end to end: the bf16 intermediate casts of the old
    # path ((x@B)@A each rounded to bf16, sparse contribs formed in bf16)
    # drifted several ulp from the densified path — enough to flip greedy
    # argmax in decode. One final rounding, like the dense path's matmul.
    xf = x.reshape(-1, d_in).astype(jnp.float32)
    y = ((xf @ B.astype(jnp.float32)) @ A.astype(jnp.float32)) * scale
    rows = rows.reshape(-1)
    cols = cols.reshape(-1)
    vf = v.reshape(-1).astype(jnp.float32)
    nnz = rows.shape[0]
    chunk = min(chunk, nnz)
    n_chunks = max(1, (nnz + chunk - 1) // chunk)
    pad = n_chunks * chunk - nnz
    rows_p = jnp.pad(rows, (0, pad)).reshape(n_chunks, chunk)
    cols_p = jnp.pad(cols, (0, pad)).reshape(n_chunks, chunk)
    v_p = jnp.pad(vf, (0, pad)).reshape(n_chunks, chunk)  # padded v == 0

    def body(acc, args):
        r, c, vv = args
        contrib = xf[:, r] * vv[None, :]                        # (N, chunk) f32
        upd = jnp.zeros((d_out, acc.shape[0]), dtype=jnp.float32)
        upd = upd.at[c].add(contrib.T)                          # segsum by col
        return acc + upd.T, None

    if n_chunks == 1:
        y, _ = body(y, (rows_p[0], cols_p[0], v_p[0]))
    else:
        y, _ = jax.lax.scan(body, y, (rows_p, cols_p, v_p))
    return y.astype(x.dtype).reshape(*lead, d_out)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _rb_rows(cols):
    d_in = cols.shape[0]
    return jnp.broadcast_to(jnp.arange(d_in, dtype=jnp.int32)[:, None], cols.shape)


def sl_matmul(x, params, consts, scale: float, exec_mode: str = "dense"):
    """Apply one SLTrain linear. params={B,A,v};
    consts={cols[,rows][,rows_t,cols_t,perm][,qv_t,rows_q,cols_q,qscale]}."""
    rb = "rows" not in consts
    if exec_mode == "quant":
        if "qv_t" not in consts:
            raise ValueError(
                "exec_mode='quant' needs quantized consts {qv_t, rows_q, "
                "cols_q, qscale} — run repro.quant.calibrate on the trained "
                "checkpoint and serve the exported artifact")
        from repro.kernels import ops
        return ops.sl_quant_decode(x, params["B"], params["A"],
                                   consts["qv_t"], consts["rows_q"],
                                   consts["cols_q"], consts["qscale"], scale)
    if exec_mode == "fused":
        if "perm" not in consts:
            raise ValueError(
                "exec_mode='fused' needs tile consts {rows_t, cols_t, perm} "
                "— init the layer with exec_mode='fused' "
                "(core.sltrain.init_params / Builder.linear)")
        from repro.kernels import ops
        return ops.sl_linear(x, params["B"], params["A"], params["v"],
                             consts["rows_t"], consts["cols_t"],
                             consts["perm"], scale)
    if exec_mode == "sparse":
        rows = _rb_rows(consts["cols"]) if rb else consts["rows"]
        return _sl_matmul_sparse(x, params["B"], params["A"], params["v"],
                                 rows, consts["cols"], scale)
    if rb:
        return _sl_matmul_rb(x, params["B"], params["A"], params["v"],
                             consts["cols"], scale)
    return _sl_matmul_coo(x, params["B"], params["A"], params["v"],
                          (consts["rows"], consts["cols"]), scale)


def materialize(params, consts, scale: float):
    """Densified W (for export / tests)."""
    if "rows" not in consts:
        return densify_rb(params["B"], params["A"], params["v"],
                          consts["cols"], scale)
    return densify_coo(params["B"], params["A"], params["v"],
                       consts["rows"], consts["cols"], scale)


def param_count(d_in: int, d_out: int, rank: int, delta: float,
                support_kind: str = "row_balanced") -> Tuple[int, int]:
    """(trainable, index) parameter counts — paper's (d+p)r + δdp."""
    nnz = support_lib.nnz_for(d_in, d_out, delta, support_kind)
    return (d_in + d_out) * rank + nnz, nnz
