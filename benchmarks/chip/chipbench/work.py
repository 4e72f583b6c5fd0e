"""Operations and bytes that SLTrain's own work needs, from shapes alone.

The counts are those of the factored layer, never of the densified
d_in×d_out matrix: a linear needs 2·m·(r·(d_in+d_out)+nnz) FLOPs for the
forward and as many for dx, and 2·m·nnz for dv. Bytes are what the call
must read and write at least once: activations and factors in the
configuration's dtype, the support values in that dtype and their column
indices as int32, and dv in float32. Any implementation of the layer does
at least this work, so a share of the roofline computed from it cannot
pass 100% whatever implements the linear.
"""
from __future__ import annotations

from chipbench import spec

BF16, I32, F32 = 2, 4, 4


def sl_matmul(m: int, d_in: int, d_out: int, r: int, nnz: int) -> dict:
    """y = x·(scale·B·A ⊕ V) for m rows; also dx = dy·Wᵀ with the roles of
    d_in and d_out swapped."""
    return {"flops": 2 * m * (r * (d_in + d_out) + nnz),
            "bytes": BF16 * (m * d_in + m * d_out + r * (d_in + d_out) + nnz)
            + I32 * nnz}


def sddmm(m: int, d_in: int, d_out: int, nnz: int) -> dict:
    """dv = (xᵀ·dy) sampled at the support, for m rows."""
    return {"flops": 2 * m * nnz,
            "bytes": BF16 * (m * d_in + m * d_out) + I32 * nnz + F32 * nnz}


def least_seconds(call: dict, peaks: dict) -> float:
    """The least time a chip can take for a call: the larger of its FLOPs
    over peak FLOP/s and its bytes over peak bandwidth."""
    return max(call["flops"] / peaks["bf16_flops_per_s"],
               call["bytes"] / peaks["hbm_bytes_per_s"])


def train_sl_calls(cfg: dict, m: int) -> list:
    """The SL kernel calls of one training step over m tokens: per layer
    and linear, the forward, dx (a transposed forward) and dv."""
    calls = []
    for _ in range(spec.dims(cfg)["layers"]):
        for lin in spec.linears(cfg):
            di, do, r, nnz = lin["d_in"], lin["d_out"], lin["rank"], lin["nnz"]
            calls += [dict(kernel="sl_matmul", **sl_matmul(m, di, do, r, nnz)),
                      dict(kernel="sl_matmul", **sl_matmul(m, do, di, r, nnz)),
                      dict(kernel="sddmm", **sddmm(m, di, do, nnz))]
    return calls


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward FLOPs per token: the SLTrain linears by their factored
    count, the head over the vocabulary held here, and the two attention
    products at causal length (a token attends on average seq/2
    positions). Norms, rotations and the embedding gather count nothing."""
    m = spec.dims(cfg)
    per_layer = sum(2 * (l["rank"] * (l["d_in"] + l["d_out"]) + l["nnz"])
                    for l in spec.linears(cfg))
    per_layer += 2 * 2 * (seq / 2) * m["heads"] * m["head_dim"]
    return m["layers"] * per_layer + 2 * m["d"] * m["vocab"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Training FLOPs per token: three times the forward (the backward
    needs two products per forward product); recompute does not count."""
    return 3 * forward_flops_per_token(cfg, seq)
