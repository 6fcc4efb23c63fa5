"""The warm-up plan reaches every shape the engine's padding can produce
for the mix's lengths."""
from bench import warmup
from bench.warmup import pages, pow2


def _simulate(p_max, chunk, budget, page):
    """Every (padded chunk, table width) a lone or budget-cut chunk can
    have: any real length r <= chunk at any start s with s + r <= p_max."""
    out = set()
    for s in range(0, p_max):
        for r in range(1, min(chunk, p_max - s) + 1):
            p = pow2(r)
            out.add((p, pow2(pages(s + p, page))))
    return out


def test_prefill_shapes_match_simulation():
    got = warmup.prefill_shapes(16, 200, 32, 16)
    assert got == _simulate(200, 32, 40, 16)


def test_plan_requests_land_on_their_shape():
    plan = warmup.plan(32, 2048, 512, chunk=256, page=16)
    shapes = {(p, c) for p, c, _, _ in plan.requests}
    assert shapes == warmup.prefill_shapes(32, 2048, 256, 16)
    widths = set()
    for p, c, s, max_new in plan.requests:
        assert pow2(pages(s + p, 16)) == c and s % 16 == 0
        assert s <= plan.base_len
        if max_new == 2:
            widths.add(pow2(pages(s + p + 1, 16)))
    assert widths == set(plan.decode_widths)
