"""The sharding spec engine: PartitionSpecs from pytree paths (DESIGN §4).

Single owner of every mesh/sharding decision in the repo:

* **Mesh construction** — :func:`make_production_mesh` (16×16 single-pod,
  2×16×16 multi-pod) and :func:`make_local_mesh` (every visible device).
* **Ambient-mesh probing** — :func:`ambient_mesh` / :func:`constrain`, the
  degrading ``with_sharding_constraint`` used inside model code (moved
  here from ``models/common.py`` so model files carry no mesh logic).
* **Spec derivation** — :func:`spec_for_param` maps a pytree path + leaf
  to a PartitionSpec; :func:`param_specs` / :func:`batch_specs` /
  :func:`opt_state_specs` / :func:`cache_specs` lift it over whole trees.

Placement policy (tensor-parallel output sharding + expert parallelism):

* dense ``w (d_in, d_out)`` — shard ``d_out`` over the model axis (the
  forward's output sharding; the unembed all-gathers once per step);
* SLTrain / low-rank factor ``B (d_in, r)`` — replicated (r is tiny; the
  eq.-(2) backward psums r-sized results, see ``core/sltrain.py``);
* factor ``A (r, d_out)`` — shard ``d_out`` over model, matching the
  dense-w output layout so factored and dense layers compose;
* support ``v`` / ``cols`` (row-balanced ``(d_in, k)``) — shard ``d_in``
  over model: the gather in densify is row-local, so the support shards
  with zero cross-device index traffic;
* fused-mode tile consts ``rows_t`` / ``cols_t`` / ``perm``
  ``(nkt, nnt, cap)`` int32 — shard the ``nnt`` (d_out-tile) axis over
  model, matching the A / dense-w output layout so the distributed fused
  vjp reads only local column tiles;
* quantized serve consts (repro.quant) ``qv_t`` / ``rows_q`` / ``cols_q``
  ``(nkt, nnt, cap)`` and ``qscale (nnt, TILE)`` — same ``nnt``-over-model
  placement as the fused tile consts they mirror;
* expert-stacked MoE weights — shard the expert dim over model (EP);
* norms / embeds / biases / routers — replicated.

FSDP (``ShardingConfig.fsdp``): every spec function takes ``fsdp_axes``;
when set, parameters and optimizer state additionally shard over the
data axis — the fsdp axes are appended to the first matrix dim they
divide, composing with the TP rules above without ever using a mesh axis
twice. The matching schedule (all-gather params before use,
reduce-scatter grads before the update) falls out of XLA SPMD once the
train step pins its gradients back to these specs (train/step.py,
train/perlayer.py).

Every rule is guarded: an axis that does not divide the dim falls back to
replication for that dim, never an error (heterogeneous archs × meshes).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


MODEL_AXIS = "model"
BATCH_AXES = ("pod", "data")


# ---------------------------------------------------------------------------
# Mesh construction (moved from launch/mesh.py)
# ---------------------------------------------------------------------------

def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Every visible device on the data axis (model = 1), with the
    production axis names: one chip, one host of chips, or the CPU."""
    return jax.make_mesh((jax.device_count(), 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


# ---------------------------------------------------------------------------
# Ambient-mesh probing (moved from models/common.py)
# ---------------------------------------------------------------------------

def ambient_mesh():
    """The mesh jit is tracing under, or None (CPU tests / no context)."""
    try:
        m = jax.sharding.get_abstract_mesh()
        if m.axis_names:
            return m
    except Exception:
        pass
    try:
        from jax._src.mesh import thread_resources
        m = thread_resources.env.physical_mesh
        if m.axis_names:
            return m
    except Exception:
        pass
    return None


def axis_size(mesh, names) -> int:
    """Product of the sizes of ``names`` (a name or tuple) on ``mesh``;
    names absent from the mesh count as 1."""
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    return int(np.prod([mesh.shape[a] for a in names if a in mesh.axis_names]
                       or [1]))


def constrain(x, *spec):
    """with_sharding_constraint that degrades to a no-op when the ambient
    mesh lacks the named axes or the dims don't divide. spec entries are
    axis names, tuples of names, or None, one per dim of x."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    axes = set(mesh.axis_names)
    clean = []
    for dim, s in zip(x.shape, spec):
        names = s if isinstance(s, tuple) else ((s,) if s else ())
        names = tuple(n for n in names if n in axes)
        n = axis_size(mesh, names)
        clean.append(names if (names and dim % n == 0) else None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*clean))
    except Exception:
        return x


# ---------------------------------------------------------------------------
# Path → spec rules
# ---------------------------------------------------------------------------

def _path_keys(path) -> Tuple[str, ...]:
    """Normalize a tree path (DictKey / SequenceKey / plain objects with a
    ``.key`` attribute) to a tuple of strings."""
    out = []
    for k in path:
        key = getattr(k, "key", None)
        if key is None:
            key = getattr(k, "name", None)
        if key is None:
            key = getattr(k, "idx", None)
        out.append(str(key) if key is not None else str(k))
    return tuple(out)


def _guard(dim: int, mesh, names):
    """names (as a tuple, filtered to axes the mesh has) if they divide
    ``dim``, else None (replicate that dim)."""
    if not names:
        return None
    req = names if isinstance(names, tuple) else (names,)
    tup = tuple(n for n in req if n in mesh.axis_names)
    if not tup:
        return None
    n = axis_size(mesh, tup)
    return tup if dim % max(n, 1) == 0 else None


# leaf name → spec of the TRAILING (matrix) dims; leading dims are the
# layer-stack (and expert-stack) axes handled separately.
_REPLICATED_NAMES = frozenset({
    "bias", "ln_attn", "ln_mlp", "ln_attn_post", "ln_mlp_post", "ln_f",
    "q_norm", "k_norm", "embed", "lm_head", "W0",
})


def _base_spec(name: str, keys: Tuple[str, ...], trailing: Tuple[int, ...],
               mesh, model_axis: str):
    """Spec for the trailing (non-stack) dims of one leaf."""
    nd = len(trailing)
    if name in _REPLICATED_NAMES or nd == 0:
        return (None,) * nd
    if name == "w":
        if "router" in keys:                     # routers stay replicated
            return (None,) * nd
        if nd >= 2:                              # dense W: TP output shard
            return (None,) * (nd - 1) + (_guard(trailing[-1], mesh,
                                                model_axis),)
        return (None,) * nd
    if name == "B":                              # (d_in, r): replicated
        return (None,) * nd
    if name == "A":                              # (r, d_out): TP output shard
        return (None,) * (nd - 1) + (_guard(trailing[-1], mesh, model_axis),)
    if name in ("v", "cols", "rows"):
        if nd >= 2:                              # row-balanced (d_in, k):
            return (_guard(trailing[0], mesh,    # shard d_in rows
                           model_axis),) + (None,) * (nd - 1)
        return (None,) * nd                      # iid COO (nnz,): replicate
    if name in ("rows_t", "cols_t", "perm") and nd == 3:
        # fused-mode tile consts (nkt, nnt, cap) int32: shard the nnt
        # (d_out-tile) axis over model, matching the A / dense-w output
        # sharding — each TP shard then addresses only its own column
        # tiles, and the distributed fused vjp (kernels/ops.py) consumes
        # the local slice without an all-gather.
        return (None, _guard(trailing[1], mesh, model_axis), None)
    if name in ("qv_t", "rows_q", "cols_q") and nd == 3:
        # int8 serve consts (repro.quant): same (nkt, nnt, cap) geometry
        # as the fused tile consts, same nnt-over-model placement.
        return (None, _guard(trailing[1], mesh, model_axis), None)
    if name == "qscale" and nd == 2:
        # (nnt, TILE) per-channel scales: blocked by column tile, so the
        # nnt axis shards alongside qv_t's.
        return (_guard(trailing[0], mesh, model_axis), None)
    # everything else is replicated.
    return (None,) * nd


_MATRIX_NDIM = {"w": 2, "B": 2, "A": 2, "cols": 2, "v": 2, "W0": 2,
                "embed": 2, "lm_head": 2,
                # fused tile consts are 3-D (nkt, nnt, cap); anything
                # beyond that is layer/expert stacking
                "rows_t": 3, "cols_t": 3, "perm": 3,
                # quantized serve consts (repro.quant.layout)
                "qv_t": 3, "rows_q": 3, "cols_q": 3, "qscale": 2}


def _append_fsdp(base, trailing, mesh, fsdp_axes, used):
    """Append the fsdp axes to the FIRST trailing (matrix) dim they
    divide, on top of whatever the TP rules already placed there — never
    reusing a mesh axis (``used`` = axes the lead/base spec consumed).
    Returns the augmented trailing spec, or ``base`` unchanged when no
    dim can absorb them (replicate fallback, same contract as _guard)."""
    axes = tuple(a for a in fsdp_axes
                 if a in mesh.axis_names and a not in used)
    if not axes:
        return base
    out = list(base)
    for i, dim in enumerate(trailing):
        cur = out[i] if isinstance(out[i], tuple) else (
            (out[i],) if out[i] else ())
        cand = cur + axes
        if dim % max(axis_size(mesh, cand), 1) == 0:
            out[i] = cand
            return tuple(out)
    return base


def spec_for_param(path, leaf, mesh, *, model_axis: str = MODEL_AXIS,
                   support_layout: Optional[str] = None,
                   fsdp_axes: Tuple[str, ...] = ()) -> P:
    """PartitionSpec for one parameter/const leaf addressed by tree path.

    Handles the layer-stack convention (scan-over-layers prepends a layer
    axis to every leaf) and the expert-stack convention (MoE experts add a
    second leading axis, sharded over the model axis = EP).

    ``support_layout`` disambiguates SLTrain support leaves whose shapes
    collide once layer-stacked — row-balanced ``(d_in, k)`` vs an iid COO
    ``(nnz,)`` stacked to ``(L, nnz)``: pass ``"iid"`` or
    ``"row_balanced"`` when known (:func:`param_specs` infers it from the
    presence of a sibling ``rows`` leaf); None assumes row-balanced, the
    repo default.

    ``fsdp_axes`` (``ShardingConfig.fsdp``) additionally shards the leaf
    over the data axis: the axes are appended to the first MATRIX dim
    they divide, composing with (never displacing, never double-using)
    the TP placement above. Leading layer/expert-stack dims stay
    unsharded — the per-layer sweep slices them — so fsdp lands on the
    within-layer matrix dims the TP rules left room on.
    """
    keys = _path_keys(path)
    name = keys[-1] if keys else ""
    ndim = leaf.ndim
    shape = tuple(leaf.shape)

    base_nd = min(_MATRIX_NDIM.get(name, 1), ndim)
    if name in ("v", "rows", "cols") and ndim >= 1:
        # row-balanced support is 2-D (d_in, k); iid COO support is 1-D
        # (nnz,) — layer stacking makes the two indistinguishable by shape
        if support_layout == "iid" or name == "rows":
            base_nd = 1
        else:
            base_nd = min(2, ndim)

    n_lead = ndim - base_nd
    trailing = shape[n_lead:]

    lead = [None] * n_lead
    used_model = False
    if "experts" in keys and n_lead >= 1:
        # the expert axis is the innermost leading dim (layer stacks are
        # prepended outside it): (L, E, ...) or (E, ...)
        e_spec = _guard(shape[n_lead - 1], mesh, model_axis)
        if e_spec is not None:
            lead[n_lead - 1] = e_spec
            used_model = True

    if used_model:
        base = (None,) * base_nd      # model axis already used for EP
    else:
        base = _base_spec(name, keys, trailing, mesh, model_axis)
    if fsdp_axes and base_nd > 0:
        used = set()
        for s in tuple(lead) + tuple(base):
            used.update(s if isinstance(s, tuple) else ((s,) if s else ()))
        base = _append_fsdp(base, trailing, mesh, fsdp_axes, used)
    return P(*(tuple(lead) + tuple(base)))


def param_specs(params, mesh, *, model_axis: str = MODEL_AXIS,
                fsdp_axes: Tuple[str, ...] = ()):
    """PartitionSpec pytree mirroring ``params`` (works on abstract trees)."""
    all_paths = {_path_keys(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]}

    def spec(path, leaf):
        keys = _path_keys(path)
        layout = None
        if keys and keys[-1] in ("v", "cols", "rows"):
            # an iid COO support dict carries a sibling "rows" leaf;
            # row-balanced stores implicit rows and has none
            layout = ("iid" if keys[:-1] + ("rows",) in all_paths
                      else "row_balanced")
        return spec_for_param(path, leaf, mesh, model_axis=model_axis,
                              support_layout=layout, fsdp_axes=fsdp_axes)

    return jax.tree_util.tree_map_with_path(spec, params)


def batch_specs(batch, mesh, batch_axes: Sequence[str] = BATCH_AXES):
    """Shard the leading (batch) dim of every leaf over ``batch_axes``."""
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        lead = _guard(leaf.shape[0], mesh, axes)
        return P(lead, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(spec, batch)


def opt_state_specs(opt_state, p_specs, mesh, *,
                    fsdp_axes: Tuple[str, ...] = ()):
    """Specs for an optimizer-state tree.

    Moment trees that mirror the param tree (AdamW's mu/nu) inherit the
    param leaf's spec; quantized / projected state whose shapes diverge
    (8-bit codes+scales, GaLore factors) and scalars are replicated —
    except under fsdp, where those non-mirroring leaves shard their
    leading dim over the fsdp axes when it divides (8-bit code/scale
    blocks are per-leaf flat, so a dim-0 split is always slice-aligned).
    """
    by_path = {}
    for path, spec in jax.tree_util.tree_flatten_with_path(
            p_specs, is_leaf=lambda x: isinstance(x, P))[0]:
        by_path[_path_keys(path)] = spec

    def spec(path, leaf):
        keys = _path_keys(path)
        for i in range(1, len(keys)):
            cand = by_path.get(keys[i:])
            if cand is not None and len(cand) <= leaf.ndim:
                return cand
        if fsdp_axes and leaf.ndim >= 1:
            g = _guard(leaf.shape[0], mesh, tuple(fsdp_axes))
            if g is not None:
                return P(g, *([None] * (leaf.ndim - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(spec, opt_state)


def cache_specs(cache, mesh, batch_axes: Sequence[str] = BATCH_AXES,
                *, model_axis: str = MODEL_AXIS,
                seq_sharded: bool = False, paged: bool = False,
                attn_kernel: str = "paged"):
    """KV-cache specs.

    Contiguous layout (default): leaves are (..., batch, seq, heads,
    head_dim). Batch shards over the batch axes; heads shard over the
    model axis when they divide (the TP attention layout);
    ``seq_sharded=True`` moves the model axis to the sequence dim instead
    (long-context decode).

    Paged layout (``paged=True``, serve/kv.py): leaves are pools
    (..., n_blocks, block_len, heads, head_dim) with no batch dim — every
    slot shares the pool through its block table. Heads shard over the
    model axis; the block and block_len dims stay replicated so any
    device can serve any slot's pages without cross-host index traffic.
    ``attn_kernel`` names the decode read path the layout must serve:

    * ``"gather"`` — the gathered per-slot view inherits the head
      sharding (XLA places the gather per shard);
    * ``"paged"`` — kernels/paged_attention.py fetches a page's kv
      heads together, so the SAME head sharding makes each device stream
      only its local heads' blocks; whole GQA q-head groups land with their kv
      head automatically because the wq output sharding divides by the
      identical model-axis factor. The kernel cannot split the sequence
      (block) dims across devices, so ``seq_sharded=True`` is rejected
      here rather than silently de-paging the pools at dispatch.

    The two kernels deliberately share one layout: toggling
    ``attn_kernel`` at serve time never resharded the cache. Copy-on-
    write prefix sharing (serve/kv.py refcounts) composes for free: a
    shared block is shared through the block TABLE (host-side int32), so
    attaching it to more slots never moves pool bytes — the pools keep
    this heads-over-model layout and every reader streams its local
    heads' rows of the same physical block."""
    if paged and attn_kernel == "paged" and seq_sharded:
        raise ValueError(
            "attn_kernel='paged' cannot run seq-sharded: the kernel "
            "streams whole K/V blocks per (slot, block) grid cell, so the "
            "sequence/block dims must stay replicated — use the head-"
            "sharded TP layout (default) or attn_kernel='gather'")
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    def spec(leaf):
        if leaf.ndim < 4:
            return P(*([None] * leaf.ndim))
        n_lead = leaf.ndim - 4
        d0, d1, h, _ = leaf.shape[n_lead:]
        if paged:
            tail = (None, None, _guard(h, mesh, model_axis), None)
        elif seq_sharded:
            tail = (_guard(d0, mesh, axes), _guard(d1, mesh, model_axis),
                    None, None)
        else:
            tail = (_guard(d0, mesh, axes), None,
                    _guard(h, mesh, model_axis), None)
        return P(*([None] * n_lead + list(tail)))

    return jax.tree.map(spec, cache)


def constrain_boundary(x, *, seq_sharded: bool = False):
    """Sharding constraint for a per-layer boundary-activation save
    (B, S, d) emitted by ``lm.forward_saving_boundaries``: batch dim over
    the batch axes; with ``seq_sharded`` (cfg.seq_shard_activations) the
    sequence dim additionally shards over the model axis, matching the SP
    residual layout the layer body already pinned — saving the boundary
    must not all-gather what the scan keeps sharded. Degrades to a no-op
    off-mesh (CPU tests)."""
    if seq_sharded:
        return constrain(x, BATCH_AXES, MODEL_AXIS, None)
    return constrain(x, BATCH_AXES, None, None)


def boundary_save_specs(xs, mesh, batch_axes: Sequence[str] = BATCH_AXES,
                        *, model_axis: str = MODEL_AXIS,
                        seq_sharded: bool = False,
                        fsdp_axes: Tuple[str, ...] = ()):
    """Specs for STACKED boundary saves (n_layers, B, S, d): layer dim
    replicated (the reverse sweep slices it layer by layer on every
    device), batch over the batch axes, seq optionally over model (SP).
    Under fsdp, when the batch dim could NOT absorb the batch axes (tiny
    per-host batches), the stacked layer dim shards over the fsdp axes
    instead so the saves still split — never both (no axis reuse)."""
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    def spec(leaf):
        if leaf.ndim < 3:
            return P(*([None] * leaf.ndim))
        n_lead = leaf.ndim - 3
        b, s, _ = leaf.shape[n_lead:]
        bt = _guard(b, mesh, axes)
        seq = _guard(s, mesh, model_axis) if seq_sharded else None
        lead = [None] * n_lead
        if fsdp_axes and n_lead >= 1:
            rem = tuple(a for a in fsdp_axes if a not in (bt or ()))
            g = _guard(leaf.shape[0], mesh, rem) if rem else None
            if g is not None:
                lead[0] = g
        return P(*lead, bt, seq, None)

    return jax.tree.map(spec, xs)


def named_shardings(mesh, spec_tree):
    """Map a PartitionSpec pytree to NamedShardings on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def place(tree, mesh, specs=None):
    """device_put a pytree onto ``mesh`` per a spec tree.

    ``specs`` defaults to the :func:`param_specs` rules — callers placing
    non-param trees (KV caches, optimizer state) pass the matching spec
    tree explicitly. The single placement helper every consumer (trainer,
    serve engine) goes through, so the spec↔sharding pairing lives here.
    """
    if specs is None:
        specs = param_specs(tree, mesh)
    return jax.device_put(tree, named_shardings(mesh, specs))
