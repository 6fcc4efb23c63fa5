"""Whether what the timed path served is correct.

After the window, samples drawn from the seed of the requests the engine
served tokens to (finished or still decoding) are run through the
configuration's model module's float32 reference (its `forward`) over
prompt + served tokens: greedy requests (the longest always among them,
then more until the configuration's `sample_tokens` served tokens are in)
and, where the mix samples, as many tokens of sampled requests.

  far_miss_share   greedy tokens: the share whose reference logit lies
                   more than the configuration's `far_gap` below the
                   reference's best logit at that position (the mean gap,
                   the widest gap and the share of tokens off the best are
                   printed beside it, not compared: int4 activation codes
                   amplify bfloat16 rounding, so a sound run already serves
                   many tokens a little below the best, and the float8
                   control reads only about twice its mean gap)
  outside_nucleus  sampled tokens: the share that lies outside the
                   reference's nucleus at the request's temperature and
                   top_p (the mass of the tokens the reference ranks above
                   it reaches top_p)

`judge` holds readings to the configuration's limits, the same for the
program and for the control. The control (`--control 1`) runs the
reference in float8 at the same positions; its tokens are the ones the
float8 forward puts first, judged in place of the served ones.
"""
from __future__ import annotations

import numpy as np

from .weights import seed_key


def sample(rec, seed: int, want_tokens: int, greedy: bool):
    """[(prompt, served tokens, temperature, top_p)] of requests of one
    kind that were served tokens, finished or still decoding when the
    window closed (every delivered token is an answer), drawn from the
    seed."""
    done = [r for r in rec.reqs if r.handle is not None
            and r.req.greedy == greedy and r.handle.generated]
    if not done:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7,
                                                        int(greedy)]))
    longest = max(done, key=lambda r: len(r.req.prompt)
                  + len(r.handle.generated))
    rest = [r for r in done if r is not longest]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [longest], len(longest.handle.generated)
    for r in rest:
        if n >= want_tokens:
            break
        out.append(r)
        n += len(r.handle.generated)
    return [(list(r.req.prompt), list(r.handle.generated), r.req.temperature,
             r.req.top_p) for r in out]


def streams_rows(picked):
    streams = [p + g for p, g, _, _ in picked]
    rows = [list(range(len(p) - 1, len(p) + len(g) - 1))
            for p, g, _, _ in picked]
    return streams, rows


def outside_nucleus(ref: np.ndarray, tokens: np.ndarray, temps: np.ndarray,
                    top_ps: np.ndarray) -> np.ndarray:
    """Per row, whether `tokens` lies outside the nucleus of softmax(ref /
    temperature) at top_p: the tokens more probable than it already hold
    top_p of the mass (the program's own rule: a token enters the nucleus
    while the mass before it is below top_p)."""
    z = ref / temps[:, None]
    z = z - z.max(axis=-1, keepdims=True)
    p = np.exp(z.astype(np.float64))
    p /= p.sum(axis=-1, keepdims=True)
    mine = p[np.arange(len(tokens)), tokens]
    above = np.where(p > mine[:, None], p, 0.0).sum(axis=-1)
    return above >= top_ps


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per row, how far the reference's logit of `tokens` lies below its
    best logit."""
    return ref.max(axis=-1) - ref[np.arange(len(tokens)), tokens]


def _readings(ref_g, tok_g, ref_s, tok_s, temps, top_ps, far_gap) -> dict:
    out = {"tokens_compared": int(len(tok_g)),
           "sampled_compared": int(len(tok_s))}
    if len(tok_g):
        gap = gaps(ref_g, tok_g)
        out.update(far_miss_share=float(np.mean(gap > far_gap)),
                   mean_gap=float(gap.mean()), max_gap=float(gap.max()),
                   miss_share=float(np.mean(gap > 0)))
    if len(tok_s):
        out["outside_nucleus"] = float(np.mean(
            outside_nucleus(ref_s, tok_s, temps, top_ps)))
    return out


def judge(readings: dict, corr: dict, sampled: bool):
    """→ ({name: {"value", "limit"}}, ok). Every number is compared with its
    limit; a number that could not be read fails."""
    limits = {"far_miss_share": ("<=", corr["far_miss_share"]),
              "tokens_compared": (">=", corr["min_tokens"])}
    if sampled:
        limits["outside_nucleus"] = ("<=", corr["outside_nucleus"])
        limits["sampled_compared"] = (">=", corr["min_tokens"])
    checks, ok = {}, True
    for name, (op, lim) in limits.items():
        v = readings.get(name)
        checks[name] = {"value": v, "limit": lim}
        ok = ok and v is not None and (v <= lim if op == "<=" else v >= lim)
    return checks, bool(ok)


def readings(seed: int, model, dims, greedy, sampled, max_len: int, *,
             far_gap: float, control=False):
    """→ (the program's readings, the control's readings or None, the
    per-token gaps of both for a record), from one reference pass over the
    greedy and the sampled picks together."""
    picked = greedy + sampled
    if not picked:
        return {"tokens_compared": 0, "sampled_compared": 0}, None, {}
    key = seed_key(seed)
    streams, rows = streams_rows(picked)
    ng = sum(len(g) for _, g, _, _ in greedy)
    ref = np.concatenate(model.forward(key, dims, streams, rows,
                                       max_len=max_len), axis=0)
    served = np.concatenate([np.asarray(g, np.int64)
                             for _, g, _, _ in picked])
    temps = np.concatenate([np.full(len(g), t, np.float64)
                            for _, g, t, _ in sampled] or [np.zeros(0)])
    top_ps = np.concatenate([np.full(len(g), p, np.float64)
                             for _, g, _, p in sampled] or [np.zeros(0)])
    prog = _readings(ref[:ng], served[:ng], ref[ng:], served[ng:], temps,
                     top_ps, far_gap)
    detail = {"gaps": gaps(ref[:ng], served[:ng]).tolist()}
    ctrl = None
    if control:
        lo = np.concatenate(model.forward(key, dims, streams, rows,
                                          mode="fp8", max_len=max_len))
        first = lo.argmax(axis=-1)
        ctrl = _readings(ref[:ng], first[:ng], ref[ng:], first[ng:], temps,
                         top_ps, far_gap)
        detail["control_gaps"] = gaps(ref[:ng], first[:ng]).tolist()
    return prog, ctrl, detail
