"""Logical work of a step, from the rows each request cached and the
configuration's shapes: real rows, live context, nominal bytes. Never
from padded tiles or from the program's own counters, so the numbers hold
whatever implements a kernel.

Per dispatch (one fused decode program, or one prefill chunk), per layer:

  int4 GEMMs   7 projections; ops 2·rows·K·N at the int8 peak; bytes
               K·N/2 (packed weights) + 4·N (scales) + rows·K (int8 codes)
               + 8·rows (code scale, zero) + 4·rows·N (f32 out)
  quantizers   4 distinct inputs per row (attention in, attention out,
               FFN in, FFN hidden); bytes 3 per element (bf16 in, int8
               out) + 8 per row; the b-point rotation of the FFN hidden is
               2·b flops per element at the bf16 peak
  attention    flops 4·H·dh per (row, key); bytes per sequence walked:
               keys·KH·(2·dh + 16) (int8 K and V, f32 scale and zero of
               each) + rows·H·dh·6 (bf16 q in, f32 out)

and once per dispatch the output head: 2·d·V per row whose logits are
used (each decode row; the last row of a finished prefill), at the bf16
peak. `roofline_s` of a call is max(ops / peak, bytes / bandwidth).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from .weights import PROJS, Dims

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"{PEAKS_FILE.name} with its source")
    return table[device_kind]


@dataclasses.dataclass
class Work:
    """Summed logical work and per-call roofline time (seconds)."""
    int8_ops: float = 0.0          # GEMM integer ops
    bf16_flops: float = 0.0        # attention + output head
    gemm_roof_s: float = 0.0
    quant_roof_s: float = 0.0
    attn_roof_s: float = 0.0
    dispatches: int = 0

    def add(self, o: "Work"):
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(o, f.name))

    @property
    def kernels_roof_s(self) -> float:
        return self.gemm_roof_s + self.quant_roof_s + self.attn_roof_s


def gemm(rows: int, k: int, n: int, pk: dict) -> tuple[float, float]:
    ops = 2.0 * rows * k * n
    byts = k * n / 2 + 4 * n + rows * k + 8 * rows + 4 * rows * n
    return ops, max(ops / pk["int8_ops"], byts / pk["hbm_bytes_s"])


def quantizer(rows: int, width: int, rot_block: int, pk: dict) -> float:
    flops = 2.0 * rot_block * rows * width if rot_block > 1 else 0.0
    byts = 3.0 * rows * width + 8 * rows
    return max(flops / pk["bf16_flops"], byts / pk["hbm_bytes_s"])


def attention(seqs: list[tuple[int, int]], dims: Dims, pk: dict
              ) -> tuple[float, float]:
    """seqs: (rows, keys summed over those rows, keys walked) per sequence
    → (flops, roofline seconds) for one layer."""
    H, KH, dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    flops = sum(4.0 * H * dh * row_keys for _, row_keys, _ in seqs)
    byts = sum(walked * KH * (2 * dh + 16) + rows * H * dh * 6
               for rows, _, walked in seqs)
    return flops, max(flops / pk["bf16_flops"], byts / pk["hbm_bytes_s"])


def dispatch(rows: int, seqs, logit_rows: int, dims: Dims, pk: dict) -> Work:
    """One fused dispatch over `rows` real rows whose sequences are `seqs`
    (rows, summed keys, keys walked)."""
    w = Work(dispatches=1)
    for name in PROJS:
        k, n = dims.proj_shape(name)
        ops, t = gemm(rows, k, n, pk)
        w.int8_ops += ops * dims.n_layers
        w.gemm_roof_s += t * dims.n_layers
    d, qd = dims.d_model, dims.n_heads * dims.head_dim
    q = (quantizer(rows, d, 1, pk) + quantizer(rows, qd, 1, pk)
         + quantizer(rows, d, 1, pk)
         + quantizer(rows, dims.d_ff, dims.block_size, pk))
    w.quant_roof_s += q * dims.n_layers
    fl, t = attention(seqs, dims, pk)
    w.bf16_flops += fl * dims.n_layers + 2.0 * d * dims.vocab * logit_rows
    w.attn_roof_s += t * dims.n_layers
    return w


def step_work(step, dims: Dims, pk: dict, chunk: int) -> Work:
    """Work of one recorded engine step (see `driver.StepRecord`)."""
    total = Work()
    if step.decode_ctx:
        seqs = [(1, n, n) for n in step.decode_ctx]
        total.add(dispatch(len(step.decode_ctx), seqs, len(step.decode_ctx),
                           dims, pk))
    for start, end, completed in step.prefill:
        # one dispatch per chunk; each row attends to every key up to itself
        a = start
        while a < end:
            b = min(a + chunk, end)
            rows = b - a
            keys = rows * (a + b + 1) / 2.0
            last = b == end and completed
            total.add(dispatch(rows, [(rows, keys, b)], int(last), dims, pk))
            a = b
    return total


def window_work(steps, dims: Dims, pk: dict, chunk: int) -> Work:
    w = Work()
    for s in steps:
        w.add(step_work(s, dims, pk, chunk))
    return w
