"""Parity tests for the Pallas paged-attention decode kernel
(kernels/paged_attention.py): kernel vs the pure-jnp oracle and vs the
gather_view+dense decode path across staggered per-slot positions, partial
tail blocks, block lengths, GQA and idle/null-block slots; a multi-step
greedy-decode engine test with ``attn_kernel="paged"``; and the
poisoned-null-block regression (NaN garbage in unallocated pages must not
leak into either attention path)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, ShardingConfig
from repro.dist import sharding as shl
from repro.kernels import ops, ref
from repro.models import registry
from repro.serve import kv as kv_lib
from repro.serve.engine import ServeEngine


# ---------------------------------------------------------------------------
# Kernel vs oracle vs gathered-view dense attention
# ---------------------------------------------------------------------------

def _mk_case(rng, *, n_slots, block_len, bps, n_kv, n_heads, hd, positions):
    """Random pools + a block table covering each slot's positions.
    ``positions[s] < 0`` marks slot s idle: all-null table row, position 0
    (exactly how the scheduler parks an empty slot)."""
    n_blocks = 1 + n_slots * bps
    k_pool = jnp.asarray(rng.standard_normal((n_blocks, block_len, n_kv, hd)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((n_blocks, block_len, n_kv, hd)),
                         jnp.float32)
    table = np.zeros((n_slots, bps), np.int32)
    nid = 1
    pos = np.zeros(n_slots, np.int32)
    for s, p in enumerate(positions):
        if p < 0:
            continue                     # idle slot
        pos[s] = p
        for j in range(kv_lib.blocks_for(p + 1, block_len)):
            table[s, j] = nid
            nid += 1
    q = jnp.asarray(rng.standard_normal((n_slots, n_heads, hd)), jnp.float32)
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(pos)


def _gather_dense(q, k_pool, v_pool, table, positions, *, scale,
                  softcap=0.0, window=0):
    """The production gather path in miniature: gather_view + the
    null-row zeroing from models/attention + dense masked softmax."""
    n_slots, n_heads, hd = q.shape
    bl, n_kv = k_pool.shape[1], k_pool.shape[2]
    g = n_heads // n_kv
    k = kv_lib.gather_view(k_pool, table).astype(jnp.float32)
    v = kv_lib.gather_view(v_pool, table).astype(jnp.float32)
    live = jnp.repeat(table != 0, bl, axis=1)
    k = jnp.where(live[:, :, None, None], k, 0)
    v = jnp.where(live[:, :, None, None], v, 0)
    kpos = jnp.arange(k.shape[1], dtype=jnp.int32)
    qg = q.reshape(n_slots, n_kv, g, hd).astype(jnp.float32) * scale
    s = jnp.einsum("shgd,slhd->shgl", qg, k)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    mask = kpos[None, :] <= positions[:, None]
    if window > 0:
        mask &= (positions[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("shgl,shld->shgd", p,
                      v.swapaxes(1, 2)).reshape(n_slots, n_heads, hd)


CASES = [
    # (block_len, n_kv, n_heads, hd, positions) — staggered, partial
    # tails, idle slots (-1), GQA (n_kv < n_heads) and MHA
    (8, 2, 4, 16, [19, 7, 5, -1]),
    (8, 4, 4, 8, [0, 8, 23, 15]),
    (16, 2, 8, 8, [1, 30, 16, -1]),
    (16, 1, 4, 16, [31, 2, -1, 12]),
    (32, 2, 4, 8, [33, 63, 0, 31]),
]


@pytest.mark.parametrize("block_len,n_kv,n_heads,hd,positions", CASES)
def test_kernel_matches_ref_and_gather(block_len, n_kv, n_heads, hd,
                                       positions):
    rng = np.random.default_rng(hash((block_len, n_kv)) % 2**31)
    bps = kv_lib.blocks_for(max(positions) + 1, block_len)
    q, kp, vp, table, pos = _mk_case(
        rng, n_slots=len(positions), block_len=block_len, bps=bps,
        n_kv=n_kv, n_heads=n_heads, hd=hd, positions=positions)
    scale = hd ** -0.5
    out = ops.paged_attention(q, kp, vp, table, pos, scale=scale)
    oracle = ref.paged_attention_ref(
        q.reshape(q.shape[0], n_kv, n_heads // n_kv, hd), kp, vp, table,
        pos, scale=scale).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=1e-5, rtol=1e-5)
    dense = _gather_dense(q, kp, vp, table, pos, scale=scale)
    active = [s for s, p in enumerate(positions) if p >= 0]
    np.testing.assert_allclose(np.asarray(out)[active],
                               np.asarray(dense)[active],
                               atol=1e-5, rtol=1e-5)
    # idle slots: the kernel pins exact zeros (nothing valid to attend)
    for s, p in enumerate(positions):
        if p < 0:
            assert float(jnp.abs(out[s]).max()) == 0.0


@pytest.mark.parametrize("softcap,window", [(30.0, 0), (0.0, 6), (8.0, 12)])
def test_kernel_softcap_and_window(softcap, window):
    """gemma2-style logit softcap and sliding window, in-kernel."""
    rng = np.random.default_rng(7)
    q, kp, vp, table, pos = _mk_case(
        rng, n_slots=3, block_len=8, bps=4, n_kv=2, n_heads=4, hd=8,
        positions=[20, 9, 31])
    out = ops.paged_attention(q, kp, vp, table, pos, scale=8 ** -0.5,
                              softcap=softcap, window=window)
    dense = _gather_dense(q, kp, vp, table, pos, scale=8 ** -0.5,
                          softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


def test_poisoned_null_block_cannot_leak_kernel_level():
    """kv.gather_view's docstring says callers mask by per-slot length —
    but a masked softmax weight is 0 and 0 · NaN = NaN, so garbage in the
    null block could still poison the output through the p @ v matmul.
    Both read paths must be immune by construction (zeroed v rows)."""
    rng = np.random.default_rng(3)
    q, kp, vp, table, pos = _mk_case(
        rng, n_slots=3, block_len=8, bps=3, n_kv=2, n_heads=4, hd=8,
        positions=[12, 3, -1])
    clean_k = ops.paged_attention(q, kp, vp, table, pos, scale=8 ** -0.5)
    clean_d = _gather_dense(q, kp, vp, table, pos, scale=8 ** -0.5)
    kp = kp.at[0].set(jnp.nan)          # poison the null block
    vp = vp.at[0].set(jnp.nan)
    out_k = ops.paged_attention(q, kp, vp, table, pos, scale=8 ** -0.5)
    out_d = _gather_dense(q, kp, vp, table, pos, scale=8 ** -0.5)
    assert np.isfinite(np.asarray(out_k)).all()
    assert np.isfinite(np.asarray(out_d)).all()
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(clean_k))
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(clean_d))


# ---------------------------------------------------------------------------
# Model level: decode_step routes through the kernel
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gqa_model():
    """Tiny GQA (Hkv < H) llama so the kernel's head-group broadcast is
    exercised end-to-end (the llama_60m smoke config is MHA)."""
    cfg = ModelConfig(name="paged-gqa", family="llama", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=512, vocab_pad_multiple=64, max_seq_len=64,
                      tie_embeddings=False)
    api = registry.get_api(cfg)
    params, consts = api.init(cfg, jax.random.PRNGKey(1), seed=1)
    return cfg, api, params, consts


def test_decode_step_kernel_matches_gather(gqa_model):
    """Same cache state, same tokens: logits from attn_kernel='paged' and
    'gather' agree to f32-attention tolerance (model runs bf16)."""
    cfg, api, params, consts = gqa_model
    max_len, bl = 32, 8
    layout = kv_lib.PagedLayout.plan(2, max_len, bl)
    bt = kv_lib.BlockTable(layout, 2)
    bt.ensure(0, 7)
    bt.ensure(1, 3)
    cache = api.init_cache(cfg, 2, max_len, paged=True, block_len=bl)
    rng = np.random.default_rng(0)
    # warm the caches at staggered positions through the gather path
    pos = np.array([0, 0], np.int32)
    for t in range(6):
        toks = jnp.asarray(rng.integers(3, 400, size=(2, 1)), jnp.int32)
        active = [0] if t >= 2 else [0, 1]   # slot 1 lags (staggered)
        step_pos = jnp.asarray(pos, jnp.int32)
        _, cache = api.decode_step(cfg, params, consts, toks, cache,
                                   step_pos, block_table=bt.as_array())
        for s in active:
            pos[s] += 1
    toks = jnp.asarray([[11], [42]], jnp.int32)
    outs = {}
    for ak in ("gather", "paged"):
        c = dataclasses.replace(cfg, attn_kernel=ak)
        logits, _ = api.decode_step(c, params, consts, toks, cache,
                                    jnp.asarray(pos, jnp.int32),
                                    block_table=bt.as_array())
        outs[ak] = np.asarray(logits, np.float32)
    np.testing.assert_allclose(outs["paged"], outs["gather"],
                               atol=0.02, rtol=0.02)
    assert (outs["paged"].argmax(-1) == outs["gather"].argmax(-1)).all()


def test_engine_greedy_decode_token_for_token(gqa_model):
    """Multi-step greedy decode with attn_kernel='paged': staggered
    arrivals, mixed prompt lengths, GQA — token-for-token vs the gather
    path AND vs single-request ground truth."""
    cfg, api, params, consts = gqa_model
    prompts = [[5, 9, 11], [7, 3, 2, 8, 6], [4, 4, 13], [9, 2]]

    def run(ak, stagger=True):
        eng = ServeEngine(cfg, params, consts, n_slots=2, max_len=32,
                          paged=True, block_len=8, attn_kernel=ak)
        reqs = [eng.submit(prompts[0], max_new_tokens=6)]
        for p in prompts[1:]:
            if stagger:
                eng.step()
            reqs.append(eng.submit(p, max_new_tokens=6))
        stats = eng.run_until_drained()
        assert not stats["exhausted"]
        return [r.out for r in reqs]

    singles = []
    for p in prompts:
        eng = ServeEngine(cfg, params, consts, n_slots=1, max_len=32,
                          paged=True, block_len=8, attn_kernel="paged")
        r = eng.submit(p, max_new_tokens=6)
        eng.run_until_drained()
        singles.append(r.out)
    out_paged = run("paged")
    assert out_paged == run("gather")
    assert out_paged == singles


def test_engine_poisoned_null_block(gqa_model):
    """End-to-end regression for the kv.gather_view masking promise: NaN
    garbage planted in every layer's null block changes NOTHING on either
    decode path."""
    cfg, api, params, consts = gqa_model
    prompts = [[5, 9, 11], [7, 3, 2, 8]]
    outs = {}
    for ak in ("gather", "paged"):
        for poison in (False, True):
            eng = ServeEngine(cfg, params, consts, n_slots=2, max_len=32,
                              paged=True, block_len=8, attn_kernel=ak)
            if poison:
                eng.cache = jax.tree.map(
                    lambda a: a.at[:, 0].set(jnp.nan), eng.cache)
            reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
            eng.run_until_drained()
            outs[(ak, poison)] = [r.out for r in reqs]
        assert outs[(ak, True)] == outs[(ak, False)], ak
    assert outs[("paged", False)] == outs[("gather", False)]


def test_engine_rejects_kernel_without_paged_cache(gqa_model):
    cfg, api, params, consts = gqa_model
    with pytest.raises(ValueError, match="paged=True"):
        ServeEngine(cfg, params, consts, paged=False, attn_kernel="paged")
    with pytest.raises(ValueError, match="attn_kernel"):
        ServeEngine(cfg, params, consts, paged=True, attn_kernel="flash")


# ---------------------------------------------------------------------------
# Sharding: the kernel shares the gather path's TP cache layout
# ---------------------------------------------------------------------------

def test_cache_specs_kernel_matches_gather_layout(gqa_model):
    """Toggling attn_kernel must never reshard the pools: both paths use
    the heads-over-model TP layout, blocks replicated."""
    cfg, api, params, consts = gqa_model
    mesh = shl.make_local_mesh()
    cache = api.init_cache(cfg, 2, 32, abstract=True, paged=True, block_len=8)
    s_gather = shl.cache_specs(cache, mesh, paged=True, attn_kernel="gather")
    s_paged = shl.cache_specs(cache, mesh, paged=True, attn_kernel="paged")
    assert s_gather == s_paged
    leaf = jax.tree.leaves(s_paged, is_leaf=lambda x: hasattr(x, "index"))[0]
    assert leaf[-2:] == ("model", None)   # heads sharded, hd replicated
    assert leaf[-4:-2] == (None, None)       # block dims replicated


def test_cache_specs_kernel_rejects_seq_sharding(gqa_model):
    cfg, api, params, consts = gqa_model
    mesh = shl.make_local_mesh()
    cache = api.init_cache(cfg, 2, 32, abstract=True, paged=True, block_len=8)
    with pytest.raises(ValueError, match="seq-sharded"):
        shl.cache_specs(cache, mesh, paged=True, seq_sharded=True,
                        attn_kernel="paged")
    # the gather path still accepts the flag (paged layout ignores it)
    shl.cache_specs(cache, mesh, paged=True, seq_sharded=True,
                    attn_kernel="gather")


# ---------------------------------------------------------------------------
# Config validation (per_layer × grad_accum composes since the in-sweep
# accumulator landed — repro.train.perlayer)
# ---------------------------------------------------------------------------

def test_sharding_config_accepts_perlayer_grad_accum():
    ShardingConfig(update_mode="per_layer", grad_accum=2)   # in-sweep accum
    ShardingConfig(update_mode="per_layer", grad_accum=1)
    ShardingConfig(update_mode="global", grad_accum=4)
