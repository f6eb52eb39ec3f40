"""HLO-text analysis: collective traffic + roofline terms from compiled jits.

``cost_analysis()`` reports FLOPs and HBM bytes but NOT collective payloads;
those are parsed out of the compiled HLO here (the instructed methodology for
the §Roofline deliverable).  Works on both ``lowered.as_text()`` (stablehlo —
not used) and ``compiled.as_text()`` (post-SPMD HLO — what we parse).

Per-device semantics: post-SPMD HLO shapes are per-participant, so summed
operand bytes of a collective are the bytes each device contributes.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

COLLECTIVE_OPS = (
    "all-to-all",
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
# e.g.:  %all-to-all.1 = (f32[4,1]{...}, ...) all-to-all(%a, %b), replica_groups=...
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<type>\(.*?\)|[\w\[\]{},:/ ]*?)\s*"
    r"(?P<op>[\w\-]+)\("
)


def shape_bytes(type_str: str) -> int:
    """Total bytes of every tensor literal in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    """Per-collective-op byte counts (per participating device)."""

    bytes_by_op: dict[str, int] = field(default_factory=dict)
    count_by_op: dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_op.values())

    def summary(self) -> str:
        if not self.bytes_by_op:
            return "no collectives"
        parts = [
            f"{op}: n={self.count_by_op[op]} {self.bytes_by_op[op]/1e6:.2f}MB"
            for op in sorted(self.bytes_by_op)
        ]
        return ", ".join(parts)


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Parse collective ops + their output payload bytes from HLO text.

    Output-shape bytes are used (== received payload per device; for
    all-reduce it equals the contributed bytes; for all-gather it counts the
    gathered result, the conventional accounting for ring-bandwidth cost).
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        if "(" not in line or "=" not in line:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        op = m.group("op")
        base = op.rstrip("0123456789.").removeprefix("%")
        # normalize fused/start variants: all-gather-start, all-reduce-scatter..
        for coll in COLLECTIVE_OPS:
            if base == coll or base == coll + "-start":
                b = shape_bytes(m.group("type"))
                stats.bytes_by_op[coll] = stats.bytes_by_op.get(coll, 0) + b
                stats.count_by_op[coll] = stats.count_by_op.get(coll, 0) + 1
                break
    return stats


def collective_bytes(hlo_text: str) -> int:
    return collective_stats(hlo_text).total_bytes


# Source locations in compiled HLO text: tables of the Python call stack
# at trace time (whose line numbers differ between two call sites) and a
# stack-frame id or file/line attributes on instructions.
_LOCATION_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_LOCATION_ATTR = re.compile(
    r' (?:stack_frame_id=\d+|source_file="[^"]*"|source_(?:end_)?(?:line|column)=\d+)'
)


def strip_source_locations(hlo_text: str) -> str:
    """``compiled.as_text()`` without its source locations: two compiles of
    one program traced from different lines compare equal, while op names
    (where named scopes land) and everything else are kept."""
    out, in_table = [], False
    for line in hlo_text.splitlines():
        if line in _LOCATION_TABLES:
            in_table = True
        elif in_table and not line.strip():
            in_table = False
        elif not in_table:
            out.append(_LOCATION_ATTR.sub("", line))
    return "\n".join(out)


__all__ = ["collective_stats", "collective_bytes", "shape_bytes", "CollectiveStats",
           "COLLECTIVE_OPS", "strip_source_locations"]
