"""Shapes of a decoder LM with SLTrain linears, from a configuration file.

The configuration files (``configs/<name>.json``) use the key names of the
published ``config.json``. Everything here is read from that file alone, so
the seeded weights, the work functions and the plain reference agree on
every shape without reading any of the program's code.
"""
from __future__ import annotations

import math

def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    return {
        "d": d,
        "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg.get("head_dim") or d // heads),
        "d_ff": int(cfg["intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
    }


def rank_for(cfg: dict, d_in: int, d_out: int) -> int:
    """SLTrain rank of one matrix: the configured rank, capped at half the
    smaller side (``sltrain.rank_rule`` in the configuration file)."""
    return max(4, min(int(cfg["sltrain"]["rank"]), min(d_in, d_out) // 2))


def row_nnz(cfg: dict, d_out: int) -> int:
    """Support entries per row of a row-balanced support: round(delta·d_out)."""
    return max(1, int(round(float(cfg["sltrain"]["delta"]) * d_out)))


def linears(cfg: dict) -> list:
    """The SLTrain linears of one layer, in order, with their shapes."""
    m = dims(cfg)
    d, hd = m["d"], m["head_dim"]
    q, kv = m["heads"] * hd, m["kv_heads"] * hd
    bias = bool(cfg.get("attention_bias", False))
    out = []
    for name, d_in, d_out, b in (("wq", d, q, bias), ("wk", d, kv, bias),
                                 ("wv", d, kv, bias), ("wo", q, d, False),
                                 ("gate", d, m["d_ff"], False),
                                 ("up", d, m["d_ff"], False),
                                 ("down", m["d_ff"], d, False)):
        r = rank_for(cfg, d_in, d_out)
        k = row_nnz(cfg, d_out)
        out.append({"name": name, "d_in": d_in, "d_out": d_out, "rank": r,
                    "k": k, "nnz": d_in * k, "bias": b})
    return out


def strata(d_out: int, k: int) -> list:
    """Bounds of the k column strata a row-balanced support draws from:
    one column per stratum, so a row's columns are distinct and sorted."""
    return [math.floor(i * d_out / k) for i in range(k + 1)]
