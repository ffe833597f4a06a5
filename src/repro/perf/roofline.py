"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh) cell the three terms are derived from the
*per-device* SPMD module (what ``lowered.compile()`` returns):

* compute term    = HLO FLOPs / peak FLOP/s          (per chip)
* memory term     = HLO bytes accessed / HBM BW      (per chip)
* collective term = collective operand bytes / ICI link BW

``cost_analysis`` supplies FLOPs and bytes; collective bytes are NOT in
cost_analysis, so ``collective_bytes`` parses the compiled HLO text and sums
the operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (including ``-start`` async forms; ``-done``
halves are skipped to avoid double counting).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

# ------------------------------------------------------------------ peaks --

@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator kind."""

    flops: float       # bf16 FLOP/s per chip
    hbm_bw: float      # HBM bytes/s per chip
    ici_bw: float      # interconnect bytes/s per link
    source: str


#: published peaks keyed by ``jax.devices()[0].device_kind``
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip "
               "(four links of 400 Gbit/s = 50 GB/s each)"),
}

#: the chip the dry-run compiles for and the analytic models describe
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; a kind without published
    peaks is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; add them with their source to "
                       f"repro.perf.roofline.PEAKS")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# post-optimization HLO prints operands as bare names, so operand bytes are
# derived from the RESULT shape + the replica-group size g:
#   all-reduce:          operand = result
#   all-gather:          operand = result / g   (result is the gathered full)
#   reduce-scatter:      operand = result * g   (result is the reduced shard)
#   all-to-all / c-perm: operand = result
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[([0-9,]+)\]<=\[")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _result_bytes(result: str) -> int:
    total = 0
    for sm in _SHAPE_RE.finditer(result):
        if sm.group(1) in _DTYPE_BYTES:
            total += _shape_bytes(sm.group(1), sm.group(2))
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        ids = [x for x in m.group(1).replace(" ", "").split(",") if x]
        return max(1, len(ids))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        dims = [int(x) for x in m.group(1).split(",")]
        return max(1, dims[-1])
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind over the per-device module.

    Async ``-start`` forms are counted; their ``-done`` halves are not
    (no double counting).
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result, kind = m.group(1), m.group(2)
        rb = _result_bytes(result)
        g = _group_size(line)
        if kind == "all-gather":
            rb = rb // max(g, 1)
        elif kind == "reduce-scatter":
            rb = rb * g
        out[kind] += rb
    return out


@dataclass
class RooflineTerms:
    flops: float                     # per-device FLOPs (analytic if avail.)
    bytes_accessed: float            # per-device HBM bytes
    coll_bytes: Dict[str, int]      # per-device collective operand bytes
    n_devices: int
    model_flops: float = 0.0         # 6*N*D (global, useful FLOPs)
    hlo_flops: float = 0.0           # raw cost_analysis value (body-once)
    hlo_bytes: float = 0.0
    #: whose peaks the terms divide by (``TARGET_KIND`` for the dry-run)
    device_kind: str = field(kw_only=True)

    @property
    def peaks(self) -> Peaks:
        return peaks(self.device_kind)

    @property
    def coll_total(self) -> int:
        return sum(self.coll_bytes.values())

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_total / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (global HLO FLOPs): remat/redundancy waste."""
        total = self.flops * self.n_devices
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute seconds / bound seconds (the score per cell)."""
        if self.bound_s <= 0:
            return 0.0
        useful_s = self.model_flops / self.n_devices / self.peaks.flops
        return useful_s / self.bound_s

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "bytes": self.bytes_accessed,
            "coll_bytes": dict(self.coll_bytes),
            "coll_total": self.coll_total, "n_devices": self.n_devices,
            "model_flops": self.model_flops,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def extract(compiled, n_devices: int, model_flops: float = 0.0,
            analytic=None, *, device_kind: str) -> RooflineTerms:
    """Build RooflineTerms from ``compiled``, an executable for a
    ``device_kind`` chip.

    ``analytic`` (a ``perf.analytic.CellCost``) supplies GLOBAL flops/bytes;
    when given it overrides cost_analysis (which counts while bodies once —
    see perf/analytic.py).  Collective bytes are always parsed from the HLO
    with trip-count scaling.
    """
    from .hlo_scale import scaled_collective_bytes

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    hlo_flops = float(ca.get("flops", 0.0))
    hlo_bytes = float(ca.get("bytes accessed", 0.0))
    coll = scaled_collective_bytes(compiled.as_text())
    if analytic is not None:
        flops = analytic.flops / n_devices
        nbytes = analytic.hbm_bytes / n_devices
    else:
        flops, nbytes = hlo_flops, hlo_bytes
    return RooflineTerms(flops=flops, bytes_accessed=nbytes,
                         coll_bytes=coll, n_devices=n_devices,
                         model_flops=model_flops, hlo_flops=hlo_flops,
                         hlo_bytes=hlo_bytes, device_kind=device_kind)


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE) per step.

    D = tokens processed: batch*seq for train/prefill, batch for decode.
    Training includes the backward pass (the factor 6 = 2 fwd + 4 bwd);
    prefill/decode use the forward-only factor 2.
    """
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch          # one token per sequence
    return 2.0 * n_active * tokens
