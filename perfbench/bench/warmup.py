"""Which compiled shapes a cell's traffic can reach, and requests that
reach each of them once before the window opens.

The engine pads a prefill chunk to a power of two `p` and its block table
to a power of two `c` of pages, and pads the decode batch to `max_seqs`
rows with a table of `c` pages; it compiles one program per (p, c), one
per decode `c`, and one page scrub per power-of-two page count. From the
mix's shortest and longest prompt and longest output, and the engine's
chunk and budget, `plan` lists every such shape the window can use, and
requests that reach each through the public API alone: a base prompt is
served once and cached by the prefix cache, then each request shares its
first `s` tokens (whole pages) and adds `p` fresh ones, so its one
prefill chunk lands at table width `c`; `max_new` 2 adds one decode step
at the width its context needs. Page scrubs of every power-of-two count
are warmed by allocating and releasing that many pages.
"""
from __future__ import annotations

import dataclasses


def pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pages(n: int, page: int) -> int:
    return -(-n // page)


@dataclasses.dataclass
class Plan:
    base_len: int            # tokens of the cached base prompt
    requests: list[tuple[int, int, int, int]]   # (p, c, shared s, max_new)
    decode_widths: list[int]
    scrub_counts: list[int]


def prefill_shapes(p_min: int, p_max: int, chunk: int, page: int
                   ) -> set[tuple[int, int]]:
    """(padded chunk, table width) pairs the traffic can dispatch. A chunk
    of `r` real tokens (padded to p) starts at any offset s with s + r <=
    the longest prompt; budget left-overs make every r <= chunk possible,
    at offset 0 too."""
    out = set()
    p = 1
    while p <= pow2(chunk):
        r_min = 1 if p == 1 else p // 2 + 1
        if r_min <= min(chunk, p_max):
            widths = pow2(pages(p_max - r_min + p, page))
            c = pow2(pages(p, page))
            while c <= widths:
                out.add((p, c))
                c *= 2
        p *= 2
    return out


def decode_widths(p_min: int, p_max: int, o_max: int, page: int) -> list[int]:
    """Decode table widths: a decode step writes position n (p <= n <
    p + o - 1) and its table covers n + 1 positions."""
    lo, hi = pow2(pages(p_min + 1, page)), pow2(pages(p_max + o_max - 1, page))
    out, c = [], lo
    while c <= hi:
        out.append(c)
        c *= 2
    return out


def _offset(p: int, c: int, page: int) -> int:
    """Smallest page-aligned shared prefix s with pow2(pages(s + p)) == c."""
    if pow2(pages(p, page)) == c:
        return 0
    s = -(-(page * (c // 2) - p + 1) // page) * page
    assert pow2(pages(s + p, page)) == c, (p, c, s)
    return s


def plan(p_min: int, p_max: int, o_max: int, *, chunk: int, page: int
         ) -> Plan:
    shapes = sorted(prefill_shapes(p_min, p_max, chunk, page))
    reqs = [[p, c, _offset(p, c, page), 1] for p, c in shapes]
    widths = decode_widths(p_min, p_max, o_max, page) if o_max > 1 else []
    for w in widths:
        # a prefill request whose one decode step lands at width w
        hit = next((r for r in reqs if r[3] == 1
                    and pow2(pages(r[2] + r[0] + 1, page)) == w), None)
        if hit is None:
            # none lands there: one more request with a longer prefix
            for p, c in shapes:
                s = max(0, -(-(page * (w // 2) - p) // page) * page)
                if pow2(pages(s + p, page)) == c \
                        and pow2(pages(s + p + 1, page)) == w:
                    hit = [p, c, s, 1]
                    reqs.append(hit)
                    break
        if hit is None:
            raise ValueError(f"no warm-up request reaches decode width {w}")
        hit[3] = 2
    longest = max(pages(p_max + o_max, page), 1)
    scrub = []
    n = 1
    while n <= pow2(longest):
        scrub.append(n)
        n *= 2
    return Plan(base_len=max(r[2] for r in reqs),
                requests=[tuple(r) for r in reqs], decode_widths=widths,
                scrub_counts=scrub)
