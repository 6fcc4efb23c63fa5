"""One general traffic generator, driven by a mix file.

A mix file (`perfbench/traffic/<name>.json`) gives:

  kind          "open_loop" (arrivals on a schedule at `rate_rps`) or
                "backlog" (`n_requests` queued at time 0)
  prompt/output {"median", "sigma", "min", "max"}: lognormal token counts
  sampling      {"temperature", "top_p", "greedy_share"}: a `greedy_share`
                of the requests decode greedily (temperature 0, same top_p,
                so they ride the same compiled programs); the comparison
                with the reference reads those
  block         requests come in blocks of this many; every block holds
                the same lengths (stratified quantiles of the two
                lognormals, paired by a fixed shuffle) and the same
                inter-arrival gaps (quantiles of the exponential), in an
                order that is the same for every seed. So every seed
                offers the same work in the same order (a window sees
                part of a block, so a seeded order would change the work
                it sees); the token ids come from the seed.
  ramp_s, grace_s  seconds before the window opens (open loop) and after
                it closes, within which first tokens still count
  vocab_low     lowest token id drawn (ids are uniform in [vocab_low, V))

The same seed gives the same requests. The program sees only the list.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    arrival_s: float          # scheduled offset from the start of traffic
    prompt: list[int]
    max_new: int
    temperature: float
    top_p: float

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    nd = NormalDist()
    q = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = spec["median"] * np.exp(spec["sigma"] * q)
    return np.clip(np.round(v), spec["min"], spec["max"]).astype(int)


def block_pattern(mix: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The fixed per-block (prompt, output, greedy) triples, seed-free."""
    b = mix["block"]
    prompts = _lognormal_quantiles(mix["prompt"], b)
    outputs = _lognormal_quantiles(mix["output"], b)
    # fixed pairing of prompt and output quantiles (independent lengths)
    outputs = outputs[np.random.default_rng(12345).permutation(b)]
    n_greedy = int(round(mix["sampling"]["greedy_share"] * b))
    greedy = np.zeros(b, bool)
    greedy[np.random.default_rng(54321).permutation(b)[:n_greedy]] = True
    return prompts, outputs, greedy


def n_requests(mix: dict, seconds: float) -> int:
    if mix["kind"] == "backlog":
        return int(mix["n_requests"])
    span = mix["ramp_s"] + seconds + mix["grace_s"]
    return int(math.ceil(mix["rate_rps"] * span * 1.1)) + 1


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    fixed = np.random.default_rng(2024)     # the order, the same every seed
    b = mix["block"]
    prompts, outputs, greedy = block_pattern(mix)
    n = n_requests(mix, seconds)
    gaps = None
    if mix["kind"] == "open_loop":
        gaps = -np.log1p(-(np.arange(b) + 0.5) / b) / mix["rate_rps"]
    s = mix["sampling"]
    out, t = [], 0.0
    for blk in range(-(-n // b)):
        order = fixed.permutation(b)
        gorder = fixed.permutation(b) if gaps is not None else None
        for j in range(b):
            i = len(out)
            if i == n:
                break
            k = order[j]
            if gaps is not None:
                t += float(gaps[gorder[j]])
            g = bool(greedy[k])
            out.append(Req(
                rid=i, arrival_s=t if gaps is not None else 0.0,
                prompt=rng.integers(mix.get("vocab_low", 0), vocab,
                                    size=int(prompts[k])).tolist(),
                max_new=int(outputs[k]),
                temperature=0.0 if g else s["temperature"],
                top_p=s["top_p"]))
    return out


def max_lengths(mix: dict) -> tuple[int, int, int]:
    """(shortest prompt, longest prompt, longest output) the mix sends."""
    prompts, outputs, _ = block_pattern(mix)
    return int(prompts.min()), int(prompts.max()), int(outputs.max())
