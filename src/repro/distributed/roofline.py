"""Three-term roofline extraction from a compiled dry-run artifact.

  compute    = HLO_FLOPs            / (chips * peak_FLOP/s)
  memory     = HLO_bytes            / (chips * HBM_bw)
  collective = collective_bytes     / (chips * link_bw)

cost_analysis() supplies FLOPs / bytes-accessed of the SPMD-partitioned
per-device module (we multiply by chip count to report global numbers and
divide back in the terms).  Collective bytes are NOT in cost_analysis: we
parse the optimized HLO text and sum the tensor sizes flowing through every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
(Result-shape bytes; for all-reduce/all-to-all/permute this equals operand
bytes, for all-gather it upper-bounds the wire volume by n/(n-1).)
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict, Optional

from repro.core.hardware import ICI_LINK_BW, TPU_V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-reduce.1 = bf16[256,4096]{1,0} all-reduce(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
# tuple-result collectives:  = (bf16[..], bf16[..]) all-reduce(
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes summed over the module."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            out[kind] += _shape_bytes(dtype, dims)
            counts[kind] += 1
            continue
        m = _TUPLE_RE.search(line)
        if m:
            shapes, kind = m.groups()
            for sm in _SHAPE_RE.finditer(shapes):
                out[kind] += _shape_bytes(*sm.groups())
            counts[kind] += 1
    out["_counts"] = counts
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    # global quantities
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: Dict[str, int]
    model_flops: float
    # terms (seconds)
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    bottleneck: str = ""
    useful_flops_frac: float = 0.0
    per_device_bytes: Optional[int] = None   # from memory_analysis

    def finish(self):
        chips = self.chips
        self.t_compute = self.hlo_flops / (chips * TPU_V5E.flops)
        self.t_memory = self.hlo_bytes / (chips * TPU_V5E.hbm_bw)
        self.t_collective = self.coll_bytes / (chips * ICI_LINK_BW)
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        self.bottleneck = max(terms, key=terms.get)
        self.useful_flops_frac = (self.model_flops / self.hlo_flops
                                  if self.hlo_flops else 0.0)
        return self

    def to_dict(self):
        return asdict(self)


def model_flops_estimate(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode: D = new tokens only."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n * tokens
