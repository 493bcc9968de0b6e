"""Roofline terms from compiled dry-run artifacts.

  compute    = HLO_FLOPs_per_chip / peak_FLOPs
  memory     = HLO_bytes_per_chip / HBM_bw
  collective = Σ wire_bytes_per_chip(op) / ICI_bw

FLOPs/bytes come from ``compiled.cost_analysis()`` (the partitioned,
per-device module).  Collective bytes are NOT in cost_analysis: we parse
``compiled.as_text()`` (post-GSPMD optimized HLO, per-device shapes) and
price each collective with ring formulas against its replica-group size.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .hw import HardwareSpec, TPU_V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_TYPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)"
                      r"\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    operand_bytes: int
    group_size: int
    wire_bytes: float
    line: str = ""
    # one collective may be printed in several computations (a TPU
    # async collective fusion repeats it in each of its steps); the
    # copies share this id
    channel_id: Optional[int] = None


@dataclass
class CollectiveSummary:
    ops: List[CollectiveOp] = field(default_factory=list)

    @property
    def total_wire_bytes(self) -> float:
        return sum(o.wire_bytes for o in self.ops)

    def by_kind(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for o in self.ops:
            out[o.kind] = out.get(o.kind, 0.0) + o.wire_bytes
        return out

    def top(self, n: int = 5) -> List[CollectiveOp]:
        return sorted(self.ops, key=lambda o: -o.wire_bytes)[:n]


def _wire_bytes(kind: str, result: int, operand: int, g: int) -> float:
    """Ring-algorithm wire bytes per chip."""
    if g <= 1:
        return 0.0
    if kind == "all-gather":
        return (g - 1) * operand            # operand = per-chip shard
    if kind == "reduce-scatter":
        return (g - 1) * result             # result = per-chip shard
    if kind == "all-reduce":
        return 2.0 * (g - 1) / g * operand
    if kind == "all-to-all":
        return (g - 1) / g * operand
    if kind == "collective-permute":
        return float(operand)
    return float(operand)


def parse_collectives(hlo_text: str) -> CollectiveSummary:
    """Parse the optimized (post-partitioning) HLO for collective ops."""
    summary = CollectiveSummary()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # a tuple result may hold TPU layouts, ``{0,1:T(8,128)}``: one
        # level of nested parentheses
        m = re.search(r"=\s*(\((?:[^()]|\([^()]*\))*\)|\S+)\s+"
                      r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                      r"collective-permute)(-start)?\(", stripped)
        if not m:
            continue
        kind = m.group(2)
        # skip the -done halves of async pairs (priced at -start)
        if re.search(r"(all-reduce|all-gather|collective-permute|"
                     r"reduce-scatter|all-to-all)-done", stripped):
            continue
        result_part = stripped[:m.end(1)]
        operand_part = stripped[m.end(0) - 1:]
        result_bytes = sum(_shape_bytes(d, s)
                           for d, s in _TYPE_RE.findall(result_part))
        operand_bytes = sum(_shape_bytes(d, s)
                            for d, s in _TYPE_RE.findall(operand_part))
        gm = _GROUPS_RE.search(stripped)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(stripped)
            g = int(gi.group(2)) if gi else 1
        # async -start results wrap (operand, result, …): prefer operands
        if operand_bytes == 0:
            operand_bytes = result_bytes
        cm = _CHANNEL_RE.search(stripped)
        summary.ops.append(CollectiveOp(
            kind=kind, result_bytes=result_bytes,
            operand_bytes=operand_bytes, group_size=g,
            wire_bytes=_wire_bytes(kind, result_bytes, operand_bytes, g),
            line=stripped[:160],
            channel_id=int(cm.group(1)) if cm else None))
    return summary


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_per_chip: float
    hlo_bytes_per_chip: float
    collective_bytes_per_chip: float
    model_flops: float                 # analytic useful FLOPs (global)
    model_bytes: float = 0.0           # analytic minimal HBM traffic (global)
    peak_flops: float = 197e12
    hbm_bw: float = 819e9
    ici_bw: float = 50e9
    memory_per_chip: Dict[str, float] = field(default_factory=dict)
    collectives_by_kind: Dict[str, float] = field(default_factory=dict)
    top_collectives: List[str] = field(default_factory=list)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops_per_chip / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes_per_chip / self.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_chip / self.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs × chips): recompute/redundancy waste."""
        total_hlo = self.hlo_flops_per_chip * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU bound: useful-compute time / step lower bound."""
        t_useful = self.model_flops / (self.chips * self.peak_flops)
        return t_useful / self.t_bound if self.t_bound else 0.0

    @property
    def bandwidth_fraction(self) -> float:
        """For memory-bound (decode) cells: useful-bytes time / bound.

        Useful bytes = the data the op *must* stream (params + caches once);
        1.0 means the step streams nothing it doesn't have to."""
        if not self.model_bytes:
            return 0.0
        t_useful = self.model_bytes / (self.chips * self.hbm_bw)
        return t_useful / self.t_bound if self.t_bound else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops_per_chip": self.hlo_flops_per_chip,
            "hlo_bytes_per_chip": self.hlo_bytes_per_chip,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "bandwidth_fraction": self.bandwidth_fraction,
            "model_bytes": self.model_bytes,
            "memory_per_chip": self.memory_per_chip,
            "collectives_by_kind": self.collectives_by_kind,
            "top_collectives": self.top_collectives,
        }


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     chips: int, model_flops: float, model_bytes: float = 0.0,
                     bf16_model: bool = True,
                     hw: HardwareSpec = TPU_V5E) -> RooflineReport:
    from .hlo_walk import walk_hlo
    text = compiled.as_text()
    walked = walk_hlo(text, f32_collectives_as_bf16=bf16_model)
    #                         trip-count-aware (XLA's own cost_analysis
    #                           prices while bodies once — wrong for
    #                           scan-over-layers; see hlo_walk docstring)
    mem = compiled.memory_analysis()
    mem_dict = {
        "argument_bytes": float(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": float(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": float(getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes": float(getattr(mem, "temp_size_in_bytes", 0)) +
        float(getattr(mem, "argument_size_in_bytes", 0)),
    }
    by_kind: Dict[str, float] = {}
    agg: Dict[tuple, List[float]] = {}
    for c in walked.collectives:
        by_kind[c.kind] = by_kind.get(c.kind, 0.0) + c.wire_bytes * c.count
        key = (c.kind, c.group_size, round(c.wire_bytes))
        agg.setdefault(key, [0.0])[0] += c.count
    top = sorted(agg.items(), key=lambda kv: -kv[0][2] * kv[1][0])[:6]
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops_per_chip=walked.flops, hlo_bytes_per_chip=walked.bytes,
        collective_bytes_per_chip=sum(by_kind.values()),
        model_flops=model_flops, model_bytes=model_bytes,
        peak_flops=hw.peak_flops_bf16, hbm_bw=hw.hbm_bandwidth,
        ici_bw=hw.ici_link_bandwidth * hw.ici_links,
        memory_per_chip=mem_dict,
        collectives_by_kind=by_kind,
        top_collectives=[f"{k[0]} g={k[1]} {k[2]/1e6:.1f}MB ×{int(v[0])}"
                         for k, v in top],
    )


def model_flops_estimate(cfg, shape) -> float:
    """Analytic useful FLOPs (global, per step) — 6·N_active·D for train,
    2·N_active·tokens (+ attention/cache terms) for decode."""
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    hd = cfg.resolved_head_dim
    if shape.kind == "train":
        base = 6.0 * n_active * tokens
        # attention: fwd 4·S²·H·hd per layer per seq (QK^T + PV), ×3 for bwd
        if cfg.family not in ("ssm",):
            window = cfg.sliding_window or shape.seq_len
            eff = min(window, shape.seq_len)
            attn = (12.0 * cfg.n_layers * cfg.n_heads * hd *
                    shape.seq_len * eff * shape.global_batch)
            if cfg.family == "hybrid":
                attn *= (cfg.n_layers // cfg.attn_every) / cfg.n_layers
            base += attn
        return base
    if shape.kind == "prefill":
        base = 2.0 * n_active * tokens
        if cfg.family not in ("ssm",):
            window = cfg.sliding_window or shape.seq_len
            eff = min(window, shape.seq_len)
            attn = (4.0 * cfg.n_layers * cfg.n_heads * hd *
                    shape.seq_len * eff * shape.global_batch)
            if cfg.family == "hybrid":
                attn *= (cfg.n_layers // cfg.attn_every) / cfg.n_layers
            base += attn
        return base
    # decode: one token over the whole batch
    base = 2.0 * n_active * shape.global_batch
    if cfg.family not in ("ssm",):
        ctx = min(cfg.sliding_window or shape.seq_len, shape.seq_len)
        layers_with_attn = (cfg.n_layers // cfg.attn_every
                            if cfg.family == "hybrid" else cfg.n_layers)
        base += (4.0 * layers_with_attn * cfg.n_heads * hd * ctx *
                 shape.global_batch)
    if cfg.family in ("ssm", "hybrid") and cfg.ssm:
        d_inner = cfg.ssm.expand * cfg.d_model
        base += 6.0 * cfg.n_layers * d_inner * cfg.ssm.state * \
            shape.global_batch
    return base


def model_bytes_estimate(cfg, shape) -> float:
    """Analytic minimal HBM traffic per step (global).

    Train: params read + grads written + opt state r/w (≈16 B/param) +
    activations written once forward (d_model stream per token).
    Decode: active params read once + KV/SSM cache read once.
    """
    elt = 2.0  # bf16
    n_active = cfg.active_param_count()
    n_total = cfg.param_count()
    tokens = shape.global_batch * shape.seq_len
    hd = cfg.resolved_head_dim
    if shape.kind == "train":
        opt = 16.0 * n_total            # fp32 master/m/v read+write
        act = 2.0 * elt * tokens * cfg.d_model * max(cfg.n_layers, 1)
        return elt * (n_total + n_active) + opt + act
    if shape.kind == "prefill":
        act = 2.0 * elt * tokens * cfg.d_model * max(cfg.n_layers, 1)
        return elt * n_active + act
    # decode: stream params + cache once
    cache = 0.0
    if cfg.family not in ("ssm",):
        ctx = min(cfg.sliding_window or shape.seq_len, shape.seq_len)
        layers_with_attn = (cfg.n_layers // cfg.attn_every
                            if cfg.family == "hybrid" else cfg.n_layers)
        cache += (2.0 * layers_with_attn * cfg.n_kv_heads * hd * ctx *
                  shape.global_batch * elt)
    if cfg.family in ("ssm", "hybrid") and cfg.ssm:
        d_inner = cfg.ssm.expand * cfg.d_model
        cache += (4.0 * cfg.n_layers * d_inner * cfg.ssm.state *
                  shape.global_batch)  # f32 state read+write
    return elt * n_active + cache
