"""Logical work of a step, from the rows each request cached and the
configuration's shapes: real rows, live context, nominal bytes. Never
from padded tiles or from the program's own counters, so the numbers hold
whatever implements a kernel.

A model module's `step_work(step, dims, peaks, chunk)` counts its own
layers into a `Work`, from the per-call formulas here:

  int4 GEMM    ops 2·rows·K·N at the int8 peak; bytes K·N/2 (packed
               weights) + 4·N (scales) + rows·K (int8 codes) + 8·rows
               (code scale, zero) + 4·rows·N (f32 out)
  quantizer    bytes 3 per element (bf16 in, int8 out) + 8 per row; a
               b-point rotation is 2·b flops per element at the bf16 peak
  attention    flops 4·H·dh per (row, key); bytes per sequence walked:
               keys·KH·(2·dh + 16) (int8 K and V, f32 scale and zero of
               each) + rows·H·dh·6 (bf16 q in, f32 out)

`roofline_s` of a call is max(ops / peak, bytes / bandwidth).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"{PEAKS_FILE.name} with its source")
    return table[device_kind]


@dataclasses.dataclass
class Work:
    """Summed logical work, and roofline seconds per kind of kernel under
    the names a model module gives them (`roof`)."""
    int8_ops: float = 0.0          # GEMM integer ops
    bf16_flops: float = 0.0        # attention + output head
    roof_s: dict[str, float] = dataclasses.field(default_factory=dict)
    dispatches: int = 0

    def roof(self, name: str, seconds: float):
        self.roof_s[name] = self.roof_s.get(name, 0.0) + seconds

    def add(self, o: "Work"):
        self.int8_ops += o.int8_ops
        self.bf16_flops += o.bf16_flops
        self.dispatches += o.dispatches
        for name, s in o.roof_s.items():
            self.roof(name, s)

    @property
    def kernels_roof_s(self) -> float:
        total = 0.0      # a plain left sum: `sum` compensates its rounding
        for s in self.roof_s.values():
            total += s
        return total


def gemm(rows: int, k: int, n: int, pk: dict) -> tuple[float, float]:
    ops = 2.0 * rows * k * n
    byts = k * n / 2 + 4 * n + rows * k + 8 * rows + 4 * rows * n
    return ops, max(ops / pk["int8_ops"], byts / pk["hbm_bytes_s"])


def quantizer(rows: int, width: int, rot_block: int, pk: dict) -> float:
    flops = 2.0 * rot_block * rows * width if rot_block > 1 else 0.0
    byts = 3.0 * rows * width + 8 * rows
    return max(flops / pk["bf16_flops"], byts / pk["hbm_bytes_s"])


def attention(seqs: list[tuple[int, int]], heads: int, kv_heads: int,
              head_dim: int, pk: dict) -> tuple[float, float]:
    """seqs: (rows, keys summed over those rows, keys walked) per sequence
    → (flops, roofline seconds) for one layer."""
    H, KH, dh = heads, kv_heads, head_dim
    flops = sum(4.0 * H * dh * row_keys for _, row_keys, _ in seqs)
    byts = sum(walked * KH * (2 * dh + 16) + rows * H * dh * 6
               for rows, _, walked in seqs)
    return flops, max(flops / pk["bf16_flops"], byts / pk["hbm_bytes_s"])


def window_work(model, steps, dims, pk: dict, chunk: int) -> Work:
    """The summed `model.step_work` of recorded engine steps."""
    w = Work()
    for s in steps:
        w.add(model.step_work(s, dims, pk, chunk))
    return w
