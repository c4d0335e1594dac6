"""Roofline terms from a compiled dry-run artifact (no real hardware).

Hardware model (fixed by the brief, TPU v5e-like):
  197 TFLOP/s bf16 per chip; 819 GB/s HBM per chip; ~50 GB/s/link ICI.

Terms per (arch, shape, mesh):
  compute    = FLOPs_per_chip / 197e12
  memory     = HBM_bytes_per_chip / 819e9
  collective = link_bytes_per_chip / 50e9

FLOPs / bytes come from ``compiled.cost_analysis()`` (the post-SPMD module
is the per-partition program, so its numbers are per-chip).  Collective
bytes are NOT in cost_analysis: we parse the optimized HLO text and sum
operand/output sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, with ring-factor accounting per type.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# `bf16[128,1,2048]{2,1,0}` — possibly inside a tuple.
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return default


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    out_bytes: dict          # per-device output bytes by collective type
    link_bytes: float        # ring-model bytes crossing a device's links

    def as_dict(self) -> dict:
        return {"counts": self.counts, "out_bytes": self.out_bytes,
                "link_bytes": self.link_bytes}


def parse_collectives(hlo_text: str, default_group: int = 16) -> CollectiveStats:
    """Scan optimized (post-SPMD, per-partition) HLO for collectives.

    Ring-model per-device link bytes:
      all-reduce:        2·N·(k-1)/k    (reduce-scatter + all-gather phases)
      all-gather:        N_out·(k-1)/k  (receives everyone else's shard)
      reduce-scatter:    N_in·(k-1)/k ≈ N_out·(k-1)
      all-to-all:        N·(k-1)/k
      collective-permute: N
    """
    counts: dict[str, int] = {}
    out_bytes: dict[str, float] = {}
    link = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_txt, op = m.group(1), m.group(2)
        n = _shape_bytes(shape_txt)
        k = _group_size(line, default_group)
        counts[op] = counts.get(op, 0) + 1
        out_bytes[op] = out_bytes.get(op, 0.0) + n
        if op == "all-reduce":
            link += 2.0 * n * (k - 1) / k
        elif op == "all-gather":
            link += n * (k - 1) / k
        elif op == "reduce-scatter":
            link += n * (k - 1)
        elif op == "all-to-all":
            link += n * (k - 1) / k
        else:                       # collective-permute
            link += n
    return CollectiveStats(counts=counts, out_bytes=out_bytes,
                           link_bytes=link)


@dataclasses.dataclass
class Roofline:
    flops_per_chip: float
    hbm_bytes_per_chip: float
    link_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float          # MODEL_FLOPS / (chips · per-chip HLO flops)
    collectives: dict
    memory_analysis: dict

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def model_flops_for(meta: dict, cell_step: str) -> float:
    """Napkin MODEL_FLOPS: 6·N_active·T train, 2·N_active·T forward-only."""
    n = meta["active_params"]
    t = meta["tokens"]
    return (6.0 if cell_step == "train" else 2.0) * n * t


def analyze(compiled, meta: dict, step: str, n_chips: int,
            hlo_text: str | None = None) -> Roofline:
    from repro.launch import hlo_cost

    # XLA's own numbers (scan bodies counted once — kept for reference).
    cost = compiled.cost_analysis()
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))

    mem = {"xla_cost_flops": xla_flops, "xla_cost_bytes": xla_bytes}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, f, None)
            if v is not None:
                mem[f] = int(v)
    except Exception as e:           # pragma: no cover
        mem["error"] = str(e)

    # Trip-count-aware per-chip totals from the optimized HLO.
    text = hlo_text if hlo_text is not None else compiled.as_text()
    st = hlo_cost.analyze_text(text)
    flops = st.flops
    bytes_acc = st.hbm_bytes

    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    collective_s = st.link_bytes / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops_for(meta, step)
    ratio = mf / max(flops * n_chips, 1.0)

    return Roofline(
        flops_per_chip=flops,
        hbm_bytes_per_chip=bytes_acc,
        link_bytes_per_chip=st.link_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops=mf,
        useful_ratio=ratio,
        collectives={"counts": st.coll_counts, "out_bytes": st.coll_bytes,
                     "link_bytes": st.link_bytes},
        memory_analysis=mem,
    )
