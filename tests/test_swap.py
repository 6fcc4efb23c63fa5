"""Tiered KV paging: host swap tier unit tests + engine-level invariants.

The swap tier's contract, exercised at both layers:

  * `HostSwapPool` / `PagedKVCache.swap_out/.swap_in` — page-granular,
    bit-exact round trips through the numpy mirror; residency ledger
    transitions (device → host → device); exclusivity (shared pages
    never move); failure atomicity (an allocation failure mid-swap
    mutates nothing); the scrub/COW guards that keep host-resident and
    in-flight pages untouchable.
  * `ServeEngine` with a host tier — a pool too small for the offered
    load completes every request with tokens bit-identical to an
    unpressured baseline, whichever recovery mode pressure picks
    (swap-to-host, recompute-by-replay, or the cost model's mix);
    injected `SwapFault`s drive retry-with-backoff, then fallback to
    replay, then terminal failure past the preemption bound; the books
    (device pages, host slots, commitments) balance after every step.

The chaos test composes swap faults with the existing exhaustion /
cancel / lifecycle chaos under `FAULT_SEED`-offset seeds, mirroring
`test_faults.py`.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.paged_attention import pack_pages, unpack_pages
from repro.models import layers as L
from repro.models.transformer import build_model
from repro.serve.engine import (EngineRequest, FaultPlan, HostSwapPool,
                                SamplingParams, ServeEngine, as_servable)
from repro.serve.engine.pages import PagedKVCache

MAX_NEW = 5
PROMPTS = [[3, 14, 15, 92, 6], [53, 58, 9], [7, 9, 3, 23, 84, 62, 43],
           [41, 5, 27, 18, 2, 88, 31, 7, 64]]
GEOM = dict(n_pages=33, page_size=4, max_seqs=2, prefill_chunk=4)
# genuinely undersized: 4 usable pages < two concurrent worst cases
PRESSURE = dict(n_pages=5, page_size=4, max_seqs=2, prefill_chunk=4,
                max_preemptions=10)


# ----------------------------------------------------------------------
# cache-level units
# ----------------------------------------------------------------------

def make_cache(n_pages=8, page_size=4, nl=2, kh=2, dh=4):
    rng = np.random.default_rng(0)
    shape = (nl, n_pages, page_size, kh, dh)
    kv = {"k": jnp.asarray(rng.standard_normal(shape), jnp.float32),
          "v": jnp.asarray(rng.standard_normal(shape), jnp.float32)}
    return PagedKVCache(kv, n_pages, page_size)


def test_host_pool_capacity_and_freelist():
    cache = make_cache()
    # page_bytes: both leaves, nl * page_size * kh * dh * 4 bytes each
    assert cache.page_bytes == 2 * 2 * 4 * 2 * 4 * 4
    pool = HostSwapPool(cache.state["kv"], 5 * cache.page_bytes)
    assert pool.capacity == 5 and pool.n_free == 5 and pool.in_use == 0
    slots = pool.take(3)
    assert len(set(slots)) == 3 and pool.in_use == 3
    with pytest.raises(MemoryError, match="host swap tier exhausted"):
        pool.take(3)
    assert pool.n_free == 2           # failed take mutated nothing
    pool.release(slots[:2])
    assert pool.in_use == 1
    with pytest.raises(ValueError, match="double/invalid release"):
        pool.release([slots[0]])
    with pytest.raises(ValueError, match="double/invalid release"):
        pool.release([slots[2], slots[2]])
    assert pool.in_use == 1           # failed release mutated nothing
    # a budget smaller than one page disables the tier gracefully
    assert HostSwapPool(cache.state["kv"], 3).capacity == 0


def test_swap_roundtrip_bit_identical():
    cache = make_cache()
    cache.attach_host_pool(64)
    cache.open(0)
    cache.ensure(0, 12)               # 3 pages at page_size 4
    pages = list(cache.tables[0])
    before = {k: np.asarray(leaf[:, pages])
              for k, leaf in cache.state["kv"].items()}

    n, nbytes = cache.swap_out(0)
    assert (n, nbytes) == (3, 3 * cache.page_bytes)
    assert cache.residency(0) == ["host"] * 3
    assert cache.allocator.in_use == 0          # device copies freed
    assert cache.host_pool.in_use == 3
    assert not cache._inflight
    with pytest.raises(ValueError, match="host-resident"):
        cache.block_table_array([0], 4)
    # idempotent: nothing device-resident left to move
    assert cache.swap_out(0) == (0, 0)

    n, nbytes = cache.swap_in(0)
    assert (n, nbytes) == (3, 3 * cache.page_bytes)
    assert cache.residency(0) == ["device"] * 3
    assert cache.host_pool.in_use == 0 and not cache._inflight
    new_pages = list(cache.tables[0])
    after = {k: np.asarray(leaf[:, new_pages])
             for k, leaf in cache.state["kv"].items()}
    for k in before:
        np.testing.assert_array_equal(before[k], after[k])
    assert cache.swap_in(0) == (0, 0)
    cache.release(0)
    assert cache.allocator.in_use == 0


def test_page_format_round_trips_through_pool_machinery():
    """Rows written into int8 pages of the tile-dense format (codes +
    scale/zero rows, two layers) survive scrub → copy-on-write → swap out
    to the host tier → swap back in bit for bit, and every page a step
    does not name keeps its bytes."""
    nl, n_pages, t, kh, dh = 2, 8, 4, 2, 64
    rng = np.random.default_rng(5)
    lead = (nl, n_pages, t, kh)
    codes = jnp.zeros((*lead, dh), jnp.int8)
    ones, zeros = jnp.ones(lead, jnp.float32), jnp.zeros(lead, jnp.float32)
    cache = PagedKVCache(pack_pages(codes, codes, (ones, zeros, ones, zeros)),
                         n_pages, t)
    for rid in (0, 1):
        cache.open(rid)
        cache.ensure(rid, t)
        bt = jnp.asarray([cache.tables[rid]], jnp.int32)
        pos = jnp.arange(t)[None]
        for layer in range(nl):
            c = rng.integers(-128, 128, (2, 1, t, 1, kh, dh))
            a = rng.uniform(-2, 2, (4, 1, t, 1, kh))
            new = pack_pages(jnp.asarray(c[0], jnp.int8),
                             jnp.asarray(c[1], jnp.int8),
                             tuple(jnp.asarray(x, jnp.float32) for x in a))
            cache.pool = L.paged_write_rows(cache.pool, new,
                                            bt + layer * n_pages, pos)
    page, other = cache.tables[0][0], cache.tables[1][0]

    def snap():
        return {k: np.asarray(v) for k, v in cache.pool.items()}

    def check(before, moved):
        """`moved` maps a page to what it must hold now (an array per
        leaf); every other page must equal `before`."""
        after = snap()
        for k in after:
            rest = [q for q in range(n_pages) if q not in moved]
            np.testing.assert_array_equal(after[k][:, rest],
                                          before[k][:, rest])
            for q, want in moved.items():
                np.testing.assert_array_equal(after[k][:, q], want[k])

    written = snap()
    assert all(np.any(written[k][:, page] != 0) for k in written)
    cache.release(1)                                  # scrubs `other`
    check(written, {other: {k: np.zeros_like(v[:, other])
                            for k, v in written.items()}})
    scrubbed = snap()
    dst = cache.allocator.alloc(1)[0]
    cache.cow_copy(page, dst)
    check(scrubbed, {dst: {k: v[:, page] for k, v in written.items()}})
    copied = snap()
    cache.attach_host_pool(1)
    assert cache.swap_out(0)[0] == 1 and cache.residency(0) == ["host"]
    assert cache.swap_in(0)[0] == 1
    back = cache.tables[0][0]
    moved = {back: {k: v[:, page] for k, v in written.items()}}
    if back != page:             # the page left behind was scrubbed
        moved[page] = {k: np.zeros_like(v[:, page])
                       for k, v in written.items()}
    check(copied, moved)


def test_oracle_gather_and_scatter_follow_the_page_format():
    """The test oracles read and write pools in the page format: a
    gathered slab holds each sequence's logical rows in table order, and
    `scatter_decode_rows` puts a slab row back at (page, offset)."""
    from repro.serve.engine import pages as PG
    nl, n_pages, t, kh, dh = 2, 6, 4, 2, 64
    rng = np.random.default_rng(9)
    lead = (nl, n_pages, t, kh)
    k, v = (jnp.asarray(rng.integers(-128, 128, (*lead, dh)), jnp.int8)
            for _ in range(2))
    aux = tuple(jnp.asarray(rng.uniform(-1, 1, lead), jnp.float32)
                for _ in range(4))
    pool = pack_pages(k, v, aux)
    bt = jnp.asarray([[3, 1], [5, 2]], jnp.int32)
    sk, sv, saux = unpack_pages(PG.gather_pages(pool, bt, kv_heads=kh), kh)
    for got, want in zip((sk, sv, *saux), (k, v, *aux)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want[:, bt]).reshape(got.shape))
    slab = PG.gather_pages(pool, bt, kv_heads=kh)
    # slot 0's position 5 (page 1, row 1) and slot 1's position 2 (page
    # 5, row 2) go to page 4, rows 0 and 3
    out = PG.scatter_decode_rows(pool, slab, jnp.asarray([5, 2]),
                                 jnp.asarray([4, 4]), jnp.asarray([0, 3]),
                                 kv_heads=kh)
    ok, ov, oaux = unpack_pages(out, kh)
    for got, want in zip((ok, ov, *oaux), (k, v, *aux)):
        want = np.asarray(want).copy()
        want[:, 4, 0], want[:, 4, 3] = want[:, 1, 1], want[:, 5, 2]
        np.testing.assert_array_equal(np.asarray(got), want)


def test_shared_pages_stay_device_resident():
    cache = make_cache()
    cache.attach_host_pool(64)
    cache.open(0)
    cache.ensure(0, 12)
    shared = cache.tables[0][0]
    cache.allocator.incref([shared])  # a radix-tree (or sibling) holder
    assert cache.swap_eligible_pages(0) == cache.tables[0][1:]
    n, _ = cache.swap_out(0)
    assert n == 2
    assert cache.residency(0) == ["device", "host", "host"]
    assert cache.tables[0][0] == shared
    cache.swap_in(0)
    assert cache.residency(0) == ["device"] * 3
    cache.deref([shared])
    cache.release(0)
    assert cache.allocator.in_use == 0 and cache.host_pool.in_use == 0


def test_swap_in_alloc_failure_mutates_nothing():
    cache = make_cache(n_pages=5)     # 4 usable pages
    cache.attach_host_pool(64)
    cache.open(0)
    cache.ensure(0, 12)               # 3 pages
    cache.swap_out(0)
    cache.open(1)
    cache.ensure(1, 16)               # the other sequence takes all 4
    with pytest.raises(MemoryError):
        cache.swap_in(0)
    assert cache.residency(0) == ["host"] * 3   # table untouched
    assert cache.host_pool.in_use == 3          # host slots retained
    assert cache.allocator.in_use == 4
    cache.release(1)
    cache.swap_in(0)                  # recovers once pages free up
    assert cache.residency(0) == ["device"] * 3
    cache.release(0)
    assert cache.host_pool.in_use == 0


def test_release_returns_host_slots():
    """Releasing a swapped-out sequence (cancel/expire/degrade-to-replay
    while host-resident) returns its host slots without any device work."""
    cache = make_cache()
    cache.attach_host_pool(64)
    cache.open(0)
    cache.ensure(0, 12)
    cache.swap_out(0)
    assert cache.host_pool.in_use == 3
    cache.release(0)
    assert 0 not in cache.tables
    assert cache.host_pool.in_use == 0 and cache.allocator.in_use == 0


def test_scrub_and_cow_guards():
    cache = make_cache()
    cache.attach_host_pool(64)
    cache.open(0)
    cache.ensure(0, 12)
    page = cache.tables[0][0]
    with pytest.raises(AssertionError, match="still-referenced"):
        cache.scrub([page], None)
    cache._inflight.add(page)
    try:
        with pytest.raises(AssertionError, match="in-flight"):
            cache.cow_copy(page, cache.tables[0][1])
    finally:
        cache._inflight.discard(page)
    cache.swap_out(0)
    # a swapped page's device id was freed: COW from it must refuse
    with pytest.raises(AssertionError, match="unallocated"):
        cache.cow_copy(page, 7)


# ----------------------------------------------------------------------
# engine-level invariants
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def adapter():
    cfg = get_config("llama3-1b").reduced()
    model = build_model(cfg)
    return as_servable(model, model.init(jax.random.PRNGKey(0)))


def _submit_all(eng):
    for rid, p in enumerate(PROMPTS):
        eng.submit(EngineRequest(rid=rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=MAX_NEW)))


def _run_checked(eng):
    done = []
    while eng.queue or eng.active:
        done.extend(eng.step())
        eng.check_books()
    return {r.rid: r for r in done}


def _assert_drained(eng):
    alloc = eng.kv.allocator
    assert alloc.in_use == 0 and alloc.n_free == alloc.capacity
    assert not eng.kv.tables and not eng.kv.slots
    assert not eng._committed and eng._committed_total == 0
    hp = eng.kv.host_pool
    assert hp is None or hp.in_use == 0
    eng.check_books()


def _counter(eng, name):
    return eng.metrics.counter(name).value


@pytest.fixture(scope="module")
def baseline(adapter):
    eng = ServeEngine(adapter, **GEOM)
    _submit_all(eng)
    done = _run_checked(eng)
    _assert_drained(eng)
    assert all(done[r].outcome == "length" for r in done)
    return {r: done[r].generated for r in done}


def test_swap_under_pressure_bit_identical(adapter, baseline):
    """Policy `always` on an undersized pool: victims swap out and back
    with zero replayed tokens, every request completes, and the tokens
    match the unpressured baseline bit for bit."""
    eng = ServeEngine(adapter, **PRESSURE, swap_host_mb=8,
                      swap_policy="always")
    _submit_all(eng)
    done = _run_checked(eng)
    _assert_drained(eng)
    assert _counter(eng, "engine.swap.out") >= 1
    assert _counter(eng, "engine.swap.in") >= 1
    assert _counter(eng, "engine.swap.bytes") > 0
    assert _counter(eng, "engine.swap.fallbacks") == 0
    assert _counter(eng, "engine.replayed_prefill_tokens") == 0
    for rid, toks in baseline.items():
        assert done[rid].outcome == "length"
        assert done[rid].generated == toks, rid


def test_swap_policy_never_only_preempts(adapter, baseline):
    """`never` (even with a budget offered) keeps the recompute path:
    no host pool, zero swap traffic, preemptions as before."""
    eng = ServeEngine(adapter, **PRESSURE, swap_host_mb=8,
                      swap_policy="never")
    assert eng.kv.host_pool is None
    _submit_all(eng)
    done = _run_checked(eng)
    _assert_drained(eng)
    assert _counter(eng, "engine.preemptions") >= 1
    assert _counter(eng, "engine.swap.out") == 0
    assert _counter(eng, "engine.swap.in") == 0
    for rid, toks in baseline.items():
        assert done[rid].generated == toks, rid


@pytest.mark.parametrize("break_even,expect_swap", [
    (0.0, False),      # swap never pays: every eviction recomputes
    (1e9, True),       # swap always pays: every eviction offloads
])
def test_cost_policy_follows_break_even(adapter, baseline, break_even,
                                        expect_swap):
    eng = ServeEngine(adapter, **PRESSURE, swap_host_mb=8,
                      swap_policy="cost",
                      swap_break_even_bytes_per_token=break_even)
    _submit_all(eng)
    done = _run_checked(eng)
    _assert_drained(eng)
    if expect_swap:
        assert _counter(eng, "engine.swap.out") >= 1
        assert _counter(eng, "engine.preemptions") == 0
    else:
        assert _counter(eng, "engine.swap.out") == 0
        assert _counter(eng, "engine.preemptions") >= 1
    for rid, toks in baseline.items():
        assert done[rid].generated == toks, rid


def _drive_until_swapped_out(eng):
    """Step until the first swap-out lands; returns the next step index."""
    _submit_all(eng)
    done = []
    while _counter(eng, "engine.swap.out") == 0:
        assert eng.queue or eng.active, "run ended without any swap-out"
        done.extend(eng.step())
        eng.check_books()
    return done


def test_swap_in_faults_retry_with_backoff(adapter, baseline):
    """Transient swap-in faults are retried with backoff, not replayed:
    the victim still swaps in (zero recomputed tokens) once the tier
    heals, bit-identically."""
    eng = ServeEngine(adapter, **PRESSURE, swap_host_mb=8,
                      swap_policy="always")
    done = _drive_until_swapped_out(eng)
    s = eng._step_index
    eng.faults = FaultPlan(swap_fail_steps=(s, s + 1))
    while eng.queue or eng.active:
        done.extend(eng.step())
        eng.check_books()
    done = {r.rid: r for r in done}
    _assert_drained(eng)
    assert _counter(eng, "engine.swap.retries") >= 1
    assert _counter(eng, "engine.swap.in") >= 1
    assert _counter(eng, "engine.swap.fallbacks") == 0
    assert _counter(eng, "engine.replayed_prefill_tokens") == 0
    for rid, toks in baseline.items():
        assert done[rid].generated == toks, rid


def test_swap_out_fault_degrades_to_preempt(adapter, baseline):
    """A SwapFault during swap-out falls through to plain preemption in
    the same exhaustion event — degraded service, identical tokens."""
    eng = ServeEngine(adapter, **PRESSURE, swap_host_mb=8,
                      swap_policy="always",
                      faults=FaultPlan(swap_fail_rate=1.0))
    _submit_all(eng)
    done = _run_checked(eng)
    _assert_drained(eng)
    assert _counter(eng, "engine.swap.fallbacks") >= 1
    assert _counter(eng, "engine.preemptions") >= 1
    for rid, toks in baseline.items():
        assert done[rid].generated == toks, rid


def test_swap_in_abandoned_fails_terminally(adapter, baseline):
    """Retries exhausted → fallback to replay; past the preemption bound
    the victim fails terminally with a diagnosable reason, its host
    slots returned. Everyone else is untouched."""
    eng = ServeEngine(adapter, n_pages=5, page_size=4, max_seqs=2,
                      prefill_chunk=4, max_preemptions=0,
                      swap_host_mb=8, swap_policy="always",
                      swap_max_retries=0)
    done = _drive_until_swapped_out(eng)
    s = eng._step_index
    eng.faults = FaultPlan(swap_fail_steps=tuple(range(s, s + 64)))
    while eng.queue or eng.active:
        done.extend(eng.step())
        eng.check_books()
    done = {r.rid: r for r in done}
    _assert_drained(eng)
    failed = [r for r in done.values() if r.outcome == "failed"]
    assert len(failed) == 1
    assert "swap-in abandoned" in failed[0].failed
    assert _counter(eng, "engine.swap.fallbacks") >= 1
    for rid, req in done.items():
        if req.outcome == "length":
            assert req.generated == baseline[rid], rid


def test_drain_with_swapped_resident(adapter, baseline):
    """drain() honors a swapped-out resident: it swaps back in and
    completes (it was admitted work), never-admitted queue entries
    cancel, and every tier comes back empty."""
    eng = ServeEngine(adapter, **PRESSURE, swap_host_mb=8,
                      swap_policy="always")
    done = _drive_until_swapped_out(eng)
    done.extend(eng.drain())
    done = {r.rid: r for r in done}
    _assert_drained(eng)
    assert len(done) == len(PROMPTS)
    for rid, req in done.items():
        if req.outcome == "length":
            assert req.generated == baseline[rid], rid
        else:
            # only never-admitted queue entries may be cancelled
            assert req.outcome == "cancelled" and not req.generated
    with pytest.raises(RuntimeError, match="draining"):
        eng.submit(EngineRequest(rid=99, prompt=[1, 2],
                                 sampling=SamplingParams(max_new=1)))


def test_swap_with_prefix_cache(adapter):
    """Swap composes with the radix cache: shared (tree-held) pages stay
    device resident across a victim's swap, books balance every step,
    and the greedy tokens match a pressure-free prefix run."""
    system = list(range(40, 52))      # 3 full pages at page_size 4
    prompts = [system + p for p in PROMPTS]

    def run(**kw):
        # headroom 0 so two sequences admit concurrently on their prompt
        # pages alone; the large max_new makes decode growth (backed by
        # swap, not commitment) overflow the pressured pool
        eng = ServeEngine(adapter, page_size=4, max_seqs=2,
                          prefill_chunk=4, prefix_cache=True,
                          headroom_pages=0, max_preemptions=10, **kw)
        for rid, p in enumerate(prompts):
            eng.submit(EngineRequest(
                rid=rid, prompt=list(p),
                sampling=SamplingParams(max_new=12)))
        done = _run_checked(eng)
        return eng, {r: done[r].generated for r in done}

    _, base = run(n_pages=65)
    eng, got = run(n_pages=11, swap_host_mb=8, swap_policy="always")
    assert _counter(eng, "engine.swap.out") >= 1
    assert got == base
    eng.prefix_cache.clear()
    _assert_drained(eng)


@pytest.mark.chaos
def test_chaos_with_swap_faults(adapter, baseline):
    """Exhaustion + swap faults + lifecycle chaos, seeds offset by
    FAULT_SEED (the CI matrix dimension): after any interleaving the
    books balance every step, both tiers drain empty, every request
    reaches exactly one terminal state, and completed survivors are
    bit-identical."""
    base_seed = int(os.environ.get("FAULT_SEED", "0"))
    for seed in range(base_seed * 5, base_seed * 5 + 5):
        plan = FaultPlan(seed=seed, exhaust_rate=0.3, swap_fail_rate=0.3,
                         cancel_rate=0.2, dispatch_fail_rate=0.1)
        eng = ServeEngine(adapter, **GEOM, max_preemptions=10,
                          swap_host_mb=8, swap_policy="always",
                          faults=plan)
        _submit_all(eng)
        done = _run_checked(eng)
        _assert_drained(eng)
        assert len(done) == len(PROMPTS)
        outcomes = {rid: done[rid].outcome for rid in done}
        assert all(o in ("length", "cancelled", "expired", "failed")
                   for o in outcomes.values()), (seed, outcomes)
        c = eng.metrics
        assert (c.counter("engine.requests.finished").value
                + c.counter("engine.requests.cancelled").value
                + c.counter("engine.requests.expired").value
                + c.counter("engine.requests.failed").value) == len(PROMPTS)
        # every page that left the device tier came back or was released
        assert (c.counter("engine.swap.in").value
                <= c.counter("engine.swap.out").value)
        for rid, req in done.items():
            if req.outcome == "length":
                assert req.generated == baseline[rid], (seed, rid)


def test_swap_metrics_in_snapshot(adapter):
    """The v4 taxonomy: swap counters and host-tier gauges are present
    (and schema-valid) with and without a host pool attached."""
    from repro.serve.telemetry import validate_snapshot

    eng = ServeEngine(adapter, **GEOM)
    _submit_all(eng)
    _run_checked(eng)
    snap = eng.metrics_snapshot()
    validate_snapshot(snap)
    assert snap["gauges"]["engine.swap.host_pages_capacity"] == 0

    eng = ServeEngine(adapter, **PRESSURE, swap_host_mb=8,
                      swap_policy="always")
    _submit_all(eng)
    _run_checked(eng)
    snap = eng.metrics_snapshot()
    validate_snapshot(snap)
    c, g = snap["counters"], snap["gauges"]
    assert c["engine.swap.out"] >= 1 and c["engine.swap.in"] >= 1
    assert c["engine.swap.bytes"] > 0
    assert c["engine.swap.bytes"] % eng.kv.page_bytes == 0
    assert g["engine.swap.host_pages_capacity"] > 0
    assert g["engine.swap.host_budget_bytes"] == 8 * 2 ** 20
    assert g["engine.swap.host_pages"] == 0      # drained
