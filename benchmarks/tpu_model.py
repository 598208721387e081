"""TPU-model latency from compiled HLO — the device-side analogue of the
paper's NPU measurements.

CPU wall-clock CANNOT reproduce the paper's relative claims: a CPU executes
gathers well (the paper says exactly this — control-heavy work belongs on
CPUs) and pays O(N^2) for the dense rewrites, so the comparison inverts.
The paper's speedups come from the accelerator's asymmetry: MXU-class dense
throughput vs DSP-class serialized gather/scatter.

We therefore derive a modelled latency from each path's ACTUAL compiled
artifact (same methodology as launch/roofline.py): HLO FLOPs at MXU rate,
HBM bytes at full bandwidth, EXCEPT bytes moved by gather / scatter /
dynamic-slice ops, which are priced at GATHER_BW — the serialized
row-granularity DMA rate that models the NPU's DSP path (and the TPU's own
poor gather throughput). INT8 dots get the 2x MXU rate (QuantGr's claim).

One backend artifact is repriced: the CPU emitter lowers s8 dots by
materializing s32 COPIES of the int8 operands (convert(s8)->s32 feeding the
dot), where an MXU/NPU int8 datapath reads the 1-byte operands natively.
`analyze` subtracts the excess: 3 B/element for every s32 operand of every
s32-accumulating dot (4B artifact read vs 1B native — counted per dot, so
an operand converted once but read by several dots, like the cached int8 Â
feeding both GCN layers, is repriced at every use), plus 5 B/element per
widening convert (its 1B read + 4B write simply don't exist natively).
Without this, QuantGr's operand-byte shrink — the entire point of shipping
int8 Â (DESIGN.md §8) — would be invisible to the model. The repricing
assumes s32 dots ARE quantized-int8 dots, which holds for every path in
this repo (nothing dots genuine int32 data).

The GNN paths contain no scans (heads unroll), so HLO cost analysis is
exact here — no two-point correction needed.
"""
from __future__ import annotations

import re
from typing import Callable, Dict

import jax

from repro.core.costs import GATHER_BW, HBM_BW, PEAK_BF16  # noqa: F401

PEAK_INT8 = 2 * PEAK_BF16     # int8 dots at the 2x MXU rate (QuantGr)
VPU_RATE = PEAK_BF16 / 8      # elementwise/transcendental fallback

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "f64": 8}

_GATHER_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\b(gather|scatter|dynamic-slice|"
    r"dynamic-update-slice)\(", )

_INT8_DOT_RE = re.compile(r"=\s*s32\[[\d,]*\][^=]*?\bdot\(")

# CPU lowering artifact: s8 dot operands widened to s32 copies (see module
# docstring) — native int8 datapaths read the 1-byte form directly.
_S8_WIDEN_RE = re.compile(r"=\s*s32\[([\d,]*)\][^=]*?\bconvert\(s8\[")
_S32_DOT_OPERANDS_RE = re.compile(       # operands carry {layout} commas
    r"=\s*s32\[[\d,]*\][^=]*?\bdot\(s32\[([\d,]*)\]\S*\s+%[^,]+,"
    r"\s+s32\[([\d,]*)\]")


def _bytes_of(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def analyze(fn: Callable, *args) -> Dict[str, float]:
    """Compile fn(*args) and derive the TPU-model latency terms."""
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()

    gather_bytes = 0
    for m in _GATHER_RE.finditer(txt):
        gather_bytes += _bytes_of(m.group(1), m.group(2))
    has_int8_dot = bool(_INT8_DOT_RE.search(txt))

    flops = float(ca.get("flops", 0.0))
    trans = float(ca.get("transcendentals", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    if has_int8_dot:
        excess = sum(5.0 * _bytes_of("s8", m.group(1))  # element counts
                     for m in _S8_WIDEN_RE.finditer(txt))
        excess += sum(3.0 * (_bytes_of("s8", m.group(1))
                             + _bytes_of("s8", m.group(2)))
                      for m in _S32_DOT_OPERANDS_RE.finditer(txt))
        byts = max(byts - excess, 0.0)

    t_mxu = flops / (PEAK_INT8 if has_int8_dot else PEAK_BF16)
    t_vpu = trans / VPU_RATE
    t_hbm = max(byts - gather_bytes, 0.0) / HBM_BW
    t_gather = gather_bytes / GATHER_BW
    # dense terms overlap (roofline max); the serialized gather path does not
    t_model = max(t_mxu + t_vpu, t_hbm) + t_gather
    return {"t_model_s": t_model, "t_mxu_s": t_mxu, "t_vpu_s": t_vpu,
            "t_hbm_s": t_hbm, "t_gather_s": t_gather,
            "gather_bytes": gather_bytes,
            "flops": flops, "bytes": byts, "int8": has_int8_dot}
