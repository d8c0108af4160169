"""Roofline terms from the compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

``cost_analysis()`` supplies FLOPs and bytes; collective bytes are NOT
in cost_analysis, so they are parsed from the post-SPMD HLO text: we sum
the larger of (result bytes, operand bytes) over every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute
instruction (= payload per participating device for ring algorithms, a
deliberate ~1-2x-accurate proxy; EXPERIMENTS.md reports the convention).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float        # per chip, bf16
    hbm_bw: float            # bytes/s per chip
    link_bw: float           # bytes/s per ICI link


# One TPU v5e chip, from Google Cloud documentation, "TPU v5e" page:
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI over
# four links (50 GB/s each).
HW_V5E = Hardware(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    if not dims:
        return nb
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n * nb


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum payload bytes per collective kind from post-SPMD HLO text."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # instruction lines look like:  %name = TYPE[dims] op-name(args...)
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        if re.search(rf"\b{kind}-done\(", rhs):
            continue  # counted at -start
        # result may be a tuple: take all shapes before the op token,
        # operands after it
        op_pos = rhs.find(kind)
        res_shapes = _SHAPE_RE.findall(rhs[:op_pos])
        arg_shapes = _SHAPE_RE.findall(rhs[op_pos:])
        res_b = sum(_shape_bytes(d, s) for d, s in res_shapes)
        arg_b = sum(_shape_bytes(d, s) for d, s in arg_shapes)
        out[kind] += max(res_b, arg_b)
        counts[kind] += 1
    out["_counts"] = counts
    return out


# ------------------------------------------------------------ model flops

def active_param_count(params_shape, n_experts: int = 0, top_k: int = 0) -> tuple[int, int]:
    """(total, active) parameter counts from an abstract params pytree.

    Expert leaves (paths containing 'moe/w_in'/'moe/w_out') contribute
    total*topk/E to the active count; everything else is fully active.
    """
    import jax

    total = 0
    active = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shape)[0]:
        s = "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)
        n = int(np.prod(leaf.shape))
        total += n
        if ("moe" in s) and ("w_in" in s or "w_out" in s):
            frac = top_k / max(1, n_experts)
            active += n * frac
        else:
            active += n
    return total, int(active)


def model_flops(cfg, shape, params_shape) -> float:
    """6·N_active·D for training; 2·N_active·tokens for decode/prefill fwd."""
    total, active = active_param_count(params_shape, cfg.n_experts, cfg.top_k)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    tokens = shape.global_batch * 1
    return 2.0 * active * tokens


@dataclasses.dataclass
class RooflineReport:
    """Three-term roofline for one (arch, shape, mesh).

    NOTE on conventions: JAX's ``compiled.cost_analysis()`` and the
    post-SPMD HLO report *per-device* quantities (the partitioned
    module).  The spec formulas divide *global* quantities by ``chips``;
    both phrasings are identical, so we store per-device numbers and the
    terms come out as  per_device_X / per_chip_rate.  Global HLO FLOPs
    (= per_device * chips) are reported alongside for the
    MODEL_FLOPS / HLO_FLOPs usefulness ratio.
    """
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device_hbm: float
    coll_bytes_per_device: float
    collective_detail: dict
    model_flops_: float
    compute_s: float
    memory_s: float
    collective_s: float
    peak_bytes_per_device: Optional[float] = None

    @property
    def hlo_flops_global(self) -> float:
        return self.flops_per_device * self.chips

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flop_ratio(self) -> float:
        g = self.hlo_flops_global
        return self.model_flops_ / g if g else float("nan")

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_gflops_global": self.hlo_flops_global / 1e9,
            "model_gflops": self.model_flops_ / 1e9,
            "hbm_gbytes_per_dev": self.bytes_per_device_hbm / 1e9,
            "coll_gbytes_per_dev": self.coll_bytes_per_device / 1e9,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flop_ratio": self.useful_flop_ratio,
        }


def roofline_terms(*, arch: str, shape, mesh_name: str, chips: int,
                   cost: dict, hlo_text: str, cfg, params_shape,
                   hw: Hardware = HW_V5E,
                   bytes_per_device: float | None = None) -> RooflineReport:
    flops = float(cost.get("flops", 0.0))          # per device
    nbytes = float(cost.get("bytes accessed", 0.0))  # per device
    coll = collective_bytes_from_hlo(hlo_text)       # per device payloads
    coll_total = float(sum(v for k, v in coll.items() if not k.startswith("_")))
    mf = model_flops(cfg, shape, params_shape)       # global
    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device_hbm=nbytes,
        coll_bytes_per_device=coll_total,
        collective_detail=coll, model_flops_=mf,
        compute_s=flops / hw.peak_flops,
        memory_s=nbytes / hw.hbm_bw,
        collective_s=coll_total / hw.link_bw,
        peak_bytes_per_device=bytes_per_device,
    )
