"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
ref.py pure-jnp oracle (assignment requirement for every Pallas kernel)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import support as support_lib
from repro.kernels import ops, ref
from repro.optim import quant


def _mk(d_in, d_out, r, m, delta, dtype, seed=0):
    rng = np.random.default_rng(seed)
    rows, cols = support_lib.sample_support(seed + 1, d_in, d_out, delta,
                                            "row_balanced")
    v = (rng.standard_normal(rows.shape[0]) * 0.05).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((m, d_in)), dtype)
    B = jnp.asarray(rng.standard_normal((d_in, r)) * 0.05, dtype)
    A = jnp.asarray(rng.standard_normal((r, d_out)) * 0.05, dtype)
    tiles = ops.prepare_tiles(rows, cols, v, d_in, d_out)
    return x, B, A, jnp.asarray(rows), jnp.asarray(cols), \
        jnp.asarray(v).astype(dtype), tiles


SHAPES = [
    (128, 128, 16, 64, 0.03),     # single tile
    (256, 384, 32, 200, 0.03),    # multi-tile, non-square, unaligned m
    (130, 250, 8, 64, 0.05),      # dims not tile multiples (padding path)
    (512, 256, 64, 128, 0.01),    # sparse-light
]


@pytest.mark.parametrize("d_in,d_out,r,m,delta", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sl_matmul_matches_oracle(d_in, d_out, r, m, delta, dtype):
    x, B, A, rows, cols, v, (v_t, r_t, c_t, perm) = _mk(
        d_in, d_out, r, m, delta, dtype)
    y = ops.sl_matmul(x, B, A, v_t, r_t, c_t, 0.25)
    y_ref = ref.sl_matmul_ref(x, B, A, rows, cols, v, 0.25)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("d_in,d_out,r,m,delta", SHAPES[:3])
def test_sddmm_matches_oracle(d_in, d_out, r, m, delta):
    x, B, A, rows, cols, v, (v_t, r_t, c_t, perm) = _mk(
        d_in, d_out, r, m, delta, jnp.float32)
    dy = jnp.asarray(np.random.default_rng(1).standard_normal((m, d_out)),
                     jnp.float32)
    dv_t = ops.sddmm(x, dy, r_t, c_t)
    dv_ref = ref.sddmm_ref(x, dy, rows, cols)
    # map tile values back to COO order via perm
    perm_np = np.asarray(perm).reshape(-1)
    flat = np.asarray(dv_t).reshape(-1)
    recon = np.zeros(rows.shape[0], np.float32)
    mask = perm_np >= 0
    recon[perm_np[mask]] = flat[mask]
    np.testing.assert_allclose(recon, np.asarray(dv_ref), atol=1e-3,
                               rtol=1e-3)


# (m, row cap, row blocks): the default rule puts 640 rows in one block; a
# cap of 384 splits 1000 rows into three blocks of 384, the last padded
ROW_BLOCK_CASES = [(640, None, 1), (1000, 384, 3)]


def _recorded_row_blocks(kernel):
    """(rows, row_blocks) of the kernel's last ``sl.row_blocks`` instant."""
    import repro.obs
    args = [e["args"] for e in repro.obs.get_trace().events
            if e["name"] == "sl.row_blocks"
            and e["args"]["kernel"] == kernel][-1]
    return args["rows"], args["row_blocks"]


@pytest.mark.parametrize("m,row_cap,n_blocks", ROW_BLOCK_CASES)
def test_sl_matmul_row_blocks_match_oracle(m, row_cap, n_blocks):
    x, B, A, rows, cols, v, (v_t, r_t, c_t, perm) = _mk(
        256, 384, 32, m, 0.03, jnp.float32, seed=5)
    y = ops.sl_matmul(x, B, A, v_t, r_t, c_t, 0.25, row_cap=row_cap)
    assert _recorded_row_blocks("sl_matmul")[1] == n_blocks
    y_ref = ref.sl_matmul_ref(x, B, A, rows, cols, v, 0.25)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    # the same tiles spent on 128-row blocks: a row block changes no
    # product's reduction order
    y128 = ops.sl_matmul(x, B, A, v_t, r_t, c_t, 0.25, row_cap=128)
    assert _recorded_row_blocks("sl_matmul") == (128, -(-m // 128))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y128), rtol=1e-6)


@pytest.mark.parametrize("m,row_cap,n_blocks", ROW_BLOCK_CASES)
def test_sddmm_row_blocks_match_oracle(m, row_cap, n_blocks):
    x, B, A, rows, cols, v, (v_t, r_t, c_t, perm) = _mk(
        256, 384, 32, m, 0.03, jnp.float32, seed=6)
    dy = jnp.asarray(np.random.default_rng(7).standard_normal((m, 384)),
                     jnp.float32)
    dv_t = ops.sddmm(x, dy, r_t, c_t, row_cap=row_cap)
    assert _recorded_row_blocks("sddmm")[1] == n_blocks
    perm_np = np.asarray(perm).reshape(-1)
    mask = perm_np >= 0
    recon = np.zeros(rows.shape[0], np.float32)
    recon[perm_np[mask]] = np.asarray(dv_t).reshape(-1)[mask]
    np.testing.assert_allclose(recon, np.asarray(ref.sddmm_ref(
        x, dy, rows, cols)), atol=1e-3, rtol=1e-3)


def test_fused_vjp_matches_core_autodiff():
    """The pallas custom-VJP linear must produce the same gradients as the
    XLA densify path in core.sltrain (paper eq. 2)."""
    from repro.core import sltrain
    d_in, d_out, r, m, delta = 256, 384, 32, 96, 0.03
    params, consts = sltrain.init_params(
        jax.random.PRNGKey(1), d_in, d_out, r, delta, jnp.float32,
        "row_balanced", seed=7)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((m, d_in)), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((m, d_out)), jnp.float32)
    scale = 0.5

    gc = jax.grad(lambda p: jnp.sum(
        sltrain.sl_matmul(x, p, consts, scale) * dy))(params)

    cols_rb = np.asarray(consts["cols"])
    k = cols_rb.shape[1]
    rows2 = np.repeat(np.arange(d_in, dtype=np.int32), k)
    cols2 = cols_rb.reshape(-1)
    v2 = np.asarray(params["v"]).reshape(-1)
    v_t, r_t, c_t, perm = ops.prepare_tiles(rows2, cols2, v2, d_in, d_out)

    gB, gA, gvt = jax.grad(
        lambda B, A, vt: jnp.sum(
            ops.sl_linear_fused(x, B, A, vt, r_t, c_t, scale) * dy),
        argnums=(0, 1, 2))(params["B"], params["A"], v_t)

    np.testing.assert_allclose(np.asarray(gB), np.asarray(gc["B"]),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gA), np.asarray(gc["A"]),
                               atol=1e-3, rtol=1e-3)
    perm_np = np.asarray(perm).reshape(-1)
    mask = perm_np >= 0
    recon = np.zeros(rows2.shape[0], np.float32)
    recon[perm_np[mask]] = np.asarray(gvt).reshape(-1)[mask]
    np.testing.assert_allclose(recon, np.asarray(gc["v"]).reshape(-1),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("n", [256, 1000, 64 * 256 + 3])
@pytest.mark.parametrize("wd", [0.0, 0.1])
def test_adam8bit_matches_oracle(n, wd):
    rng = np.random.default_rng(int(n + wd * 10))
    p = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m0 = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
    v0 = jnp.asarray(np.abs(rng.standard_normal(n)) * 0.01, jnp.float32)
    mc, ms, _ = quant.quantize_blockwise(m0, 256, True)
    vc, vs, _ = quant.quantize_blockwise(v0, 256, False)
    kw = dict(lr=1e-3, b1=0.9, b2=0.999, bc1=0.2, bc2=0.01, eps=1e-8, wd=wd)
    newp, mc2, ms2, vc2, vs2 = ops.adam8bit_update(p, g, mc, ms, vc, vs, **kw)
    pad = (-n) % 256
    pp = jnp.pad(p, (0, pad)).reshape(-1, 256)
    gg = jnp.pad(g, (0, pad)).reshape(-1, 256)
    scalars = jnp.array([kw["lr"], kw["b1"], kw["b2"], 1 - kw["b1"],
                         1 - kw["b2"], kw["bc1"], kw["bc2"],
                         kw["eps"], kw["wd"], 0.0])
    rp, rmc, rms, rvc, rvs = ref.adam8bit_ref(
        pp, gg, mc.reshape(-1, 256), ms, vc.reshape(-1, 256), vs, scalars,
        n_valid=n)
    np.testing.assert_allclose(np.asarray(newp),
                               np.asarray(rp).reshape(-1)[:n], atol=2e-5)
    assert (np.asarray(mc2) == np.asarray(rmc)).all()
    assert (np.asarray(vc2) == np.asarray(rvc)).all()


@pytest.mark.parametrize("n", [255, 256, 257, 100, 5 * 256 + 13])
def test_adam8bit_tail_blocks_track_quant_reference(n):
    """ISSUE-4 tail audit regression: over a multi-step trajectory with
    sizes straddling q_block (q±1, single partial block, multi-block with
    tail), the fused kernel must stay BITWISE identical to the
    optim/quant.py reference round-trip — codes exactly (including the
    padded tail lanes, which the kernel now masks to zero like the
    reference's re-pad), scales to ~1 f32 ulp (FMA contraction may differ
    between the interpret-mode kernel and fused XLA), params to ulp noise.
    The padded tail must never contaminate the last real block's scale."""
    rng = np.random.default_rng(n)
    p8 = jnp.asarray(rng.standard_normal(n), jnp.float32)
    pr = p8
    mc, ms, _ = quant.quantize_blockwise(jnp.zeros(n), 256, True)
    vc, vs, _ = quant.quantize_blockwise(jnp.zeros(n), 256, False)
    mrc, mrs, vrc, vrs = mc, ms, vc, vs
    b1, b2, lr, eps = 0.9, 0.999, 0.01, 1e-8
    for t in range(1, 12):
        g = rng.standard_normal(n)
        # decay the tail block's real gradients so a pad-lane leak (the old
        # 0.5-floor round-trip) would eventually dominate the block max
        g[-(n % 256 or 256):] *= 0.5 ** t
        g = jnp.asarray(g, jnp.float32)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p8, mc, ms, vc, vs = ops.adam8bit_update(
            p8, g, mc, ms, vc, vs, lr=lr, b1=b1, b2=b2, bc1=bc1, bc2=bc2,
            eps=eps, wd=0.0)
        m = quant.dequantize_blockwise(mrc, mrs, n, (n,), True)
        v = quant.dequantize_blockwise(vrc, vrs, n, (n,), False)
        gf = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * gf
        v = b2 * v + (1 - b2) * gf * gf
        pr = pr - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        mrc, mrs, _ = quant.quantize_blockwise(m, 256, True)
        vrc, vrs, _ = quant.quantize_blockwise(v, 256, False)
        assert (np.asarray(mc) == np.asarray(mrc)).all(), t
        assert (np.asarray(vc) == np.asarray(vrc)).all(), t
        np.testing.assert_allclose(np.asarray(ms), np.asarray(mrs),
                                   rtol=1e-6, atol=0)
        np.testing.assert_allclose(np.asarray(vs), np.asarray(vrs),
                                   rtol=1e-6, atol=0)
        np.testing.assert_allclose(np.asarray(p8), np.asarray(pr), atol=1e-6)


def test_adam8bit_converges_like_fp32_adam():
    """Optimizing a quadratic with the fused 8-bit kernel should track the
    f32 Adam trajectory to within quantization error."""
    n = 512
    rng = np.random.default_rng(0)
    target = jnp.asarray(rng.standard_normal(n), jnp.float32)
    p8 = jnp.zeros(n)
    p32 = jnp.zeros(n)
    mc, ms, _ = quant.quantize_blockwise(jnp.zeros(n), 256, True)
    vc, vs, _ = quant.quantize_blockwise(jnp.zeros(n), 256, False)
    m32 = jnp.zeros(n)
    v32 = jnp.zeros(n)
    b1, b2, lr, eps = 0.9, 0.999, 0.05, 1e-8
    for t in range(1, 60):
        g8 = p8 - target
        g32 = p32 - target
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p8, mc, ms, vc, vs = ops.adam8bit_update(
            p8, g8, mc, ms, vc, vs, lr=lr, b1=b1, b2=b2, bc1=bc1, bc2=bc2,
            eps=eps, wd=0.0)
        m32 = b1 * m32 + (1 - b1) * g32
        v32 = b2 * v32 + (1 - b2) * g32 * g32
        p32 = p32 - lr * (m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps)
    err8 = float(jnp.abs(p8 - target).mean())
    err32 = float(jnp.abs(p32 - target).mean())
    assert err8 < err32 + 0.05, (err8, err32)


@pytest.mark.parametrize("d_in,d_out,m", [(256, 384, 1), (128, 128, 16),
                                          (130, 250, 7)])
def test_sparse_decode_kernel_matches_densify(d_in, d_out, m):
    """Factored decode kernel (x·B·A + x·S, S never in HBM) must equal the
    densified oracle (beyond-paper decode path, DESIGN §3)."""
    x, B, A, rows, cols, v, (v_t, r_t, c_t, perm) = _mk(
        d_in, d_out, 16, m, 0.05, jnp.float32, seed=3)
    y = ops.sl_decode(x, B, A, v_t, r_t, c_t, 0.5)
    y_ref = ref.sl_decode_ref(x, B, A, rows, cols, v, 0.5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)
