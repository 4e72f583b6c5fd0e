"""Three-term roofline model from the compiled dry-run (DESIGN §6).

    compute    t_c = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     t_m = HLO_bytes / (chips × HBM_bw)
    collective t_x = Σ wire_bytes(algo) / (chips × link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes are
NOT in cost_analysis, so we parse the post-SPMD HLO (``compiled.as_text()``)
and sum operand/result sizes of every collective op with ring-algorithm
factors:  all-reduce 2(n−1)/n · S,  all-gather/reduce-scatter (n−1)/n · S,
all-to-all (n−1)/n · S,  collective-permute 1 · S   (per participant).

Hardware model: the published per-chip peaks in :data:`PEAKS`, keyed by
jax's ``device_kind``. The dry-run models a TPU v5e fleet (:data:`V5E`).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Published per-chip peaks, keyed by device_kind
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DevicePeaks:
    flops: float               # dense bf16 FLOP/s per chip
    hbm_bw: float              # HBM bytes/s per chip
    link_bw: float             # ICI bytes/s per link
    source: str


V5E = "TPU v5 lite"
PEAKS: Dict[str, DevicePeaks] = {
    # 1,600 Gbit/s of ICI per chip, over 4 links
    V5E: DevicePeaks(197e12, 819e9, 50e9,
                     "Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(device) -> Optional[DevicePeaks]:
    """Peaks of ``device`` (a ``jax.Device``): None on the CPU, where no
    device metric is measured; an accelerator missing from :data:`PEAKS`
    is an error, never a default."""
    if device.platform == "cpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for {device.platform} device kind "
            f"{device.device_kind!r}: add it to analysis.roofline.PEAKS "
            "with its source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\]")

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("},{")[0].strip("{}")
        return len([t for t in first.split(",") if t])
    m2 = re.search(r"replica_groups=\[(\d+)(?:,(\d+))*\]<=", line)
    return 2


@dataclass
class CollectiveStats:
    """Per-kind totals. wire_bytes are GLOBAL (summed over participants)."""
    counts: Dict[str, int] = field(default_factory=dict)
    wire_bytes: Dict[str, float] = field(default_factory=dict)
    ops: List[Tuple[str, float, int]] = field(default_factory=list)

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Scan post-SPMD HLO for collective ops and sum algorithm-adjusted
    wire bytes."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        # op kind appears as `= <shape> <kind>(` or `<kind>-start(`
        kind = None
        for k in _COLL_KINDS:
            if re.search(rf"\s{k}(-start)?\(", s):
                kind = k
                break
        if kind is None:
            continue
        shapes = _SHAPE_RE.findall(s)
        if not shapes:
            continue
        # first shape token on the line is the result; the rest (inside the
        # operand parens) are operands. Tuples repeat shapes; take the result
        # for all-gather (output-sized traffic), operands otherwise.
        lhs, rhs = s.split("(", 1)
        res_shapes = _SHAPE_RE.findall(lhs)
        opd_shapes = _SHAPE_RE.findall(rhs.split("),")[0] + ")")
        res_b = sum(_shape_bytes(d, x) for d, x in res_shapes)
        opd_b = sum(_shape_bytes(d, x) for d, x in opd_shapes)
        n = max(2, _group_size(s))
        if kind == "all-reduce":
            per = 2.0 * (n - 1) / n * opd_b
        elif kind == "all-gather":
            per = (n - 1) / n * res_b
        elif kind == "reduce-scatter":
            per = (n - 1) / n * opd_b
        elif kind == "all-to-all":
            per = (n - 1) / n * opd_b
        else:  # collective-permute: one hop
            per = float(opd_b)
            n = 1
        total = per * max(1, n)
        stats.counts[kind] = stats.counts.get(kind, 0) + 1
        stats.wire_bytes[kind] = stats.wire_bytes.get(kind, 0.0) + total
        stats.ops.append((kind, total, n))
    return stats


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------

@dataclass
class Roofline:
    flops: float               # HLO FLOPs, global (sum over chips)
    hbm_bytes: float           # HLO bytes accessed, global
    wire_bytes: float          # collective wire bytes, global
    chips: int
    model_flops: float = 0.0   # 6·N·D (dense) / 6·N_active·D (MoE)
    collectives: Optional[CollectiveStats] = None
    dot_calls: float = 0.0     # dot executions incl. trip counts (remat det.)
    trip_counts: Optional[Dict[str, int]] = None

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * PEAKS[V5E].flops)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.chips * PEAKS[V5E].hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / (self.chips * PEAKS[V5E].link_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the dominant-term bound that is useful compute:
        t_compute / max(all terms). 1.0 = compute-bound at peak."""
        m = max(self.t_compute, self.t_memory, self.t_collective)
        return self.t_compute / m if m > 0 else 0.0

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste detector."""
        return self.model_flops / self.flops if self.flops else 0.0

    def row(self) -> Dict[str, float]:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "roofline_fraction": self.roofline_fraction,
            "model_flops": self.model_flops,
            "hlo_flops": self.flops,
            "useful_ratio": self.useful_flops_ratio,
        }


def from_compiled(compiled, chips: int, *, model_flops: float = 0.0,
                  hlo_text: Optional[str] = None) -> Roofline:
    """Build the roofline from an AOT-compiled executable.

    Uses the hlo_parser cost walker (NOT compiled.cost_analysis(), which
    counts scan bodies once — see analysis/hlo_parser.py). The parsed SPMD
    program is the per-device program; flops/bytes are scaled by ``chips``
    for global totals. Collective wire bytes are already global."""
    from repro.analysis import hlo_parser
    text = hlo_text if hlo_text is not None else compiled.as_text()
    pc = hlo_parser.analyze(text)
    coll = CollectiveStats(counts=dict(pc.coll_counts),
                           wire_bytes=dict(pc.coll_wire))
    rl = Roofline(
        flops=pc.flops * chips,
        hbm_bytes=pc.hbm_bytes * chips,
        wire_bytes=pc.wire_bytes,
        chips=chips,
        model_flops=model_flops,
        collectives=coll,
    )
    rl.dot_calls = pc.dot_calls
    rl.trip_counts = pc.trip_counts
    return rl


# ---------------------------------------------------------------------------
# MODEL_FLOPS (6·N·D)
# ---------------------------------------------------------------------------

def param_count_active(cfg) -> Tuple[float, float]:
    """(total_params, active_params) analytic estimate for 6·N·D."""
    d, L, V = cfg.d_model, cfg.n_layers, cfg.padded_vocab
    hd = cfg.resolved_head_dim
    attn = d * (cfg.n_heads * hd) + 2 * d * (cfg.n_kv_heads * hd) \
        + (cfg.n_heads * hd) * d
    if cfg.moe.n_experts > 0:
        e_ff = cfg.moe.d_ff_expert or cfg.d_ff
        expert = 3 * d * e_ff
        n_route = cfg.moe.n_experts
        shared = cfg.moe.n_shared_experts
        ffn_total = (n_route + shared) * expert
        ffn_active = (cfg.moe.top_k + shared) * expert
        dense_extra = cfg.moe.first_k_dense * 3 * d * (cfg.moe.d_ff_dense
                                                       or cfg.d_ff)
        n_moe_layers = L - cfg.moe.first_k_dense
        total = L * attn + n_moe_layers * ffn_total + dense_extra + 2 * V * d
        active = L * attn + n_moe_layers * ffn_active + dense_extra + 2 * V * d
        return float(total), float(active)
    ffn = 3 * d * cfg.d_ff if cfg.d_ff else 8 * d * d  # ssm-ish fallback
    total = L * (attn + ffn) + (V * d if cfg.tie_embeddings else 2 * V * d)
    return float(total), float(total)


def model_flops(cfg, n_tokens: int, kind: str = "train") -> float:
    """6·N·D for training; 2·N·D for one forward (prefill/decode)."""
    _, active = param_count_active(cfg)
    mult = 6.0 if kind == "train" else 2.0
    return mult * active * n_tokens

