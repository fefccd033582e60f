"""Compiled-HLO analysis: roofline terms from the dry-run artifacts.

The compiled module on the 512-device host platform is a *per-device* SPMD
program, so ``cost_analysis()`` FLOPs/bytes and the collective operand bytes
parsed from the HLO text are per-chip quantities:

    compute  term = flops_per_chip / peak_flops_per_chip
    memory   term = bytes_per_chip / hbm_bw
    collective term = collective_operand_bytes_per_chip / link_bw

Hardware constants (TPU v5e, from Google Cloud's "TPU v5e" documentation):
197 TFLOP/s bf16, 819 GB/s HBM, and 1,600 Gbit/s of inter-chip
interconnect, i.e. 50 GB/s on each of its four links.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

__all__ = ["HW", "Roofline", "collective_bytes", "roofline_from_compiled",
           "model_flops_total"]

# TPU v5e per-chip constants
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
LINK_BW = 50e9             # bytes/s per ICI link
HW = {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW, "link_bw": LINK_BW}

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\(?)(\w+)\[([\d,]*)\][^=]*?\b"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_WHILE_RE = re.compile(
    r"=.*?\bwhile\(.*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)"
    r"(?:.*?known_trip_count[\"':{ ]+n[\"': ]+(\d+))?")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")


def _shape_bytes(dtype: str, dims: str) -> int:
    nbytes = _DTYPE_BYTES.get(dtype)
    if nbytes is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * nbytes


def _split_computations(hlo_text: str) -> Dict[str, str]:
    """computation name -> body text (optimized-HLO text format)."""
    comps: Dict[str, list] = {}
    cur: Optional[str] = None
    entry_alias = None
    for line in hlo_text.splitlines():
        m = _COMP_HDR_RE.match(line.strip())
        if m and line.rstrip().endswith("{") and not line.startswith(" "):
            cur = m.group(1)
            comps[cur] = []
            if line.strip().startswith("ENTRY"):
                entry_alias = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
                continue
            comps[cur].append(line)
    out = {name: "\n".join(body) for name, body in comps.items()}
    if entry_alias is not None:
        out["__entry__"] = out[entry_alias]
    return out


def _trip_count(cond_body: str) -> float:
    """Heuristic: scan-lowered conds compare the ind-var to a constant."""
    consts = [int(m.group(1)) for m in
              re.finditer(r"constant\((\d+)\)", cond_body)]
    return float(max(consts)) if consts else 1.0


def _direct_collective_bytes(body: str) -> Dict[str, int]:
    """Operand bytes of collectives appearing directly in one computation.

    Optimized HLO prints operands as bare names, so operand size is derived
    from the RESULT shape per collective semantics:
      all-reduce / all-to-all / collective-permute: operand == result;
      all-gather: operand = result / group_size;
      reduce-scatter: operand = result × group_size.
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in body.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind, start = m.group(1), m.group(2), m.group(3), m.group(4)
        res = _shape_bytes(dtype, dims)
        gm = _GROUPS_RE.search(line)
        if gm:
            gsize = int(gm.group(2))
        else:
            ge = _GROUPS_EXPL_RE.search(line)
            gsize = len(ge.group(1).split(",")) if ge else 1
        if kind == "all-gather":
            res = res // max(gsize, 1)
        elif kind == "reduce-scatter":
            res = res * max(gsize, 1)
        if "_promoted" in line and dtype == "f32":
            # XLA's all-reduce-promotion pass wraps bf16 reductions in
            # f32 converts on this backend; TPUs all-reduce bf16 natively,
            # so the logical payload is half the printed f32 shape.
            res //= 2
        out[kind] += res
    return out


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Collective operand bytes with while-loop trip multiplication.

    Walks the computation graph: each computation's total = its direct
    collectives + Σ (trip_count × body total) for nested while ops +
    called-computation totals (calls/conditionals; fusions cannot contain
    collectives).
    """
    comps = _split_computations(hlo_text)
    memo: Dict[str, Dict[str, float]] = {}

    def total(name: str, stack=()) -> Dict[str, float]:
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return {k: 0.0 for k in _COLLECTIVES}
        body = comps[name]
        acc = {k: float(v) for k, v in _direct_collective_bytes(body).items()}
        for m in _WHILE_RE.finditer(body):
            cond, wbody, known = m.group(1), m.group(2), m.group(3)
            trips = (float(known) if known
                     else _trip_count(comps.get(cond, "")))
            sub = total(wbody, stack + (name,))
            for k in _COLLECTIVES:
                acc[k] += trips * sub[k]
        # non-while calls (conditional branches, custom calls with
        # to_apply) — rare in our programs; count once
        for cm in re.finditer(r"(?:call|conditional)\(.*?to_apply=%?([\w.\-]+)",
                              body):
            sub = total(cm.group(1), stack + (name,))
            for k in _COLLECTIVES:
                acc[k] += sub[k]
        memo[name] = acc
        return acc

    acc = total("__entry__")
    out = {k: int(v) for k, v in acc.items()}
    out["total"] = int(sum(acc.values()))
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-chip HLO flops
    hbm_bytes: float             # per-chip bytes accessed
    coll_bytes: float            # per-chip collective operand bytes
    model_flops: float           # 6·N_active·tokens / chips ("useful")

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / bound time (1.0 = at the roofline)."""
        t_useful = self.model_flops / PEAK_FLOPS
        return t_useful / self.t_bound if self.t_bound else 0.0

    def as_dict(self) -> Dict[str, float]:
        return {
            "flops_per_chip": self.flops,
            "hbm_bytes_per_chip": self.hbm_bytes,
            "coll_bytes_per_chip": self.coll_bytes,
            "model_flops_per_chip": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def roofline_from_compiled(compiled, n_chips: int, model_flops_total: float,
                           hlo_text: Optional[str] = None) -> Roofline:
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)["total"]
    return Roofline(flops=flops, hbm_bytes=hbm, coll_bytes=float(coll),
                    model_flops=model_flops_total / n_chips)


def model_flops_total(cfg, shape) -> float:
    """6·N_active·D tokens convention for train; 2·N_active·D for
    inference steps (no backward)."""
    from repro.models import active_param_count
    n_active = active_param_count(cfg)
    if shape.step == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.step == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
