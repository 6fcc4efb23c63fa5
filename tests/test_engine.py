"""Paged-KV continuous-batching engine: equivalence + accounting.

The engine's block-table-native data path (in-forward page writes + the
paged-attention kernel walk) must be semantically invisible — for every
adapter backend (bf16 Model, fake-quant Model, packed-int4
`QuantizedDenseLM` with bf16/int8/int4 KV pages) the engine's greedy
generations must match the dense-cache path, chunked prefill must match
stepwise decode, mid-flight admission must not perturb running sequences,
and pages must never leak across requests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import pipeline as PL
from repro.models.transformer import build_model
from repro.serve.engine import (EngineRequest, PageAllocator, SamplingParams,
                                ServeEngine, as_servable, pages_for)
from repro.serve.quantized import QuantizedDenseLM, pack_dense_params

MAX_NEW = 4
PROMPTS = [[3, 14, 15, 92, 6], [53, 58, 9], [7, 9, 3, 23, 84, 62, 43]]


@pytest.fixture(scope="module")
def stack():
    """cfg + params + PTQ result shared by every backend parametrization."""
    cfg = get_config("llama3-1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    calib = [{"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                           0, cfg.vocab),
              "labels": jnp.zeros((2, 32), jnp.int32)}]
    res = PL.quantize_model(model, params, calib,
                            PL.preset("perq_star", block_size=16,
                                      rounding="rtn", cayley_steps=2))
    return cfg, model, params, res


def _adapter(stack, backend):
    cfg, model, params, res = stack
    if backend == "bf16":
        return as_servable(model, params)
    if backend == "fake_quant":
        return as_servable(PL.build_quantized_model(model, res), res.params,
                           name="fake-quant")
    kv_bits = {"int_kvbf16": None, "int_kv8": 8, "int_kv4": 4}[backend]
    qlm = QuantizedDenseLM(cfg, block_size=16, kv_bits=kv_bits)
    return as_servable(qlm, pack_dense_params(res.params, cfg))


def _dense_greedy(adapter, prompt, max_new):
    """The existing dense-cache serving path: whole-prompt prefill + a
    stepwise decode loop over one contiguous [1, max_len] cache."""
    cache = adapter.init_cache(1, 64)
    logits, cache = adapter.forward_chunk(
        adapter.params, jnp.asarray([prompt], jnp.int32), cache,
        jnp.asarray(0, jnp.int32))
    toks = [int(jnp.argmax(logits[0, -1]))]
    steps = [np.asarray(logits[0, -1], np.float32)]
    for j in range(max_new - 1):
        lg, cache = adapter.forward_chunk(
            adapter.params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
            jnp.asarray(len(prompt) + j, jnp.int32))
        steps.append(np.asarray(lg[0, 0], np.float32))
        toks.append(int(jnp.argmax(lg[0, 0])))
    return toks, steps


def _engine_run(adapter, prompts, *, max_new=MAX_NEW, **kw):
    kw.setdefault("n_pages", 33)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seqs", 2)
    kw.setdefault("prefill_chunk", 4)
    eng = ServeEngine(adapter, record_logits=True, **kw)
    for rid, p in enumerate(prompts):
        eng.submit(EngineRequest(rid=rid, prompt=p,
                                 sampling=SamplingParams(max_new=max_new)))
    done = eng.run()
    assert len(done) == len(prompts)
    return eng, {r.rid: r for r in done}


@pytest.mark.parametrize("backend,min_corr", [
    ("bf16", 0.999),
    ("fake_quant", 0.999),
    ("int_kvbf16", 0.999),
    ("int_kv8", 0.95),
    ("int_kv4", 0.95),
])
def test_paged_engine_matches_dense_path(stack, backend, min_corr):
    """Acceptance: paged logits track the dense-cache path for all three
    adapter backends and every KV page format."""
    adapter = _adapter(stack, backend)
    _, done = _engine_run(adapter, PROMPTS)
    for rid, prompt in enumerate(PROMPTS):
        want_toks, want_logits = _dense_greedy(adapter, prompt, MAX_NEW)
        req = done[rid]
        assert req.generated == want_toks, (rid, req.generated, want_toks)
        for got, want in zip(req.step_logits, want_logits):
            assert np.corrcoef(got, want)[0, 1] >= min_corr


def test_chunked_prefill_matches_stepwise(stack):
    """Chunked prefill (4 tokens/chunk) ≡ one-token-at-a-time prefill:
    same tokens and near-identical per-step logits."""
    adapter = _adapter(stack, "bf16")
    _, chunked = _engine_run(adapter, PROMPTS, prefill_chunk=4)
    _, stepwise = _engine_run(adapter, PROMPTS, prefill_chunk=1)
    for rid in range(len(PROMPTS)):
        assert chunked[rid].generated == stepwise[rid].generated
        for a, b in zip(chunked[rid].step_logits, stepwise[rid].step_logits):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_midflight_admission_does_not_perturb(stack):
    """A sequence decoding while another is admitted and prefilled must
    produce exactly the logits it produces running alone."""
    adapter = _adapter(stack, "bf16")

    def run(with_second):
        eng = ServeEngine(adapter, n_pages=33, page_size=8, max_seqs=2,
                          prefill_chunk=4, record_logits=True)
        eng.submit(EngineRequest(rid=0, prompt=PROMPTS[0],
                                 sampling=SamplingParams(max_new=6)))
        out = []
        out += eng.step()
        out += eng.step()
        if with_second:
            eng.submit(EngineRequest(rid=1, prompt=PROMPTS[2],
                                     sampling=SamplingParams(max_new=2)))
        while eng.queue or eng.active:
            out += eng.step()
        return {r.rid: r for r in out}

    alone = run(False)
    mixed = run(True)
    assert 1 in mixed and mixed[1].done
    assert mixed[0].generated == alone[0].generated
    for a, b in zip(mixed[0].step_logits, alone[0].step_logits):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_page_accounting_no_leaks(stack):
    """Many short requests through a pool too small to hold them all at
    once: everything completes (admission queues on pages) and every page
    returns to the free list."""
    adapter = _adapter(stack, "bf16")
    prompts = [[(7 * i + j) % 500 for j in range(3 + i % 4)]
               for i in range(8)]
    # each request commits pages_for(prompt + max_new) = 1..2 pages of 8;
    # capacity 4 forces queueing behind page availability
    eng, done = _engine_run(adapter, prompts, n_pages=5, page_size=8,
                            max_seqs=2, max_new=3)
    assert eng.kv.allocator.n_free == eng.kv.allocator.capacity == 4
    assert not eng.kv.tables and not eng._committed
    assert all(len(done[i].generated) == 3 for i in range(len(prompts)))


def test_walked_pages_accounting(stack):
    """The scheduler's walked-pages counters must show the ragged
    early-exit doing strictly less work than the padded-batch ×
    full-table walk of the pre-flash-decode kernel (the benchmarks
    report exactly these counters)."""
    adapter = _adapter(stack, "bf16")
    eng, _ = _engine_run(adapter, PROMPTS)
    assert 0 < eng.pages_walked < eng.pages_walked_dense


def test_integer_kv_pages_round_trip(stack):
    """Integer KV pages carry codes + scale/zero: after a run the pool
    leaves (K|V code rows, and their scale/zero rows) keep their dtypes
    and the engine still frees cleanly."""
    adapter = _adapter(stack, "int_kv4")
    eng, done = _engine_run(adapter, PROMPTS[:2])
    assert eng.kv.pool["kv_rows"].dtype == jnp.int8
    assert eng.kv.pool["kv_scale_zero"].dtype == jnp.float32
    assert set(eng.kv.pool) == {"kv_rows", "kv_scale_zero"}
    assert eng.kv.allocator.n_free == eng.kv.allocator.capacity


def test_scheduler_dispatch_is_block_table_native():
    """Acceptance guard: the scheduler's decode/prefill dispatches must
    not gather pages into a slab or scatter rows back — the pool and the
    block tables go straight into `forward_chunk`, and the gather/scatter
    primitives survive only as the test oracle in `pages.py`."""
    import ast
    import inspect

    import repro.serve.engine.scheduler as SCH

    banned = {"gather_pages", "scatter_decode_rows", "scatter_prefill_rows"}
    for node in ast.walk(ast.parse(inspect.getsource(SCH))):
        if isinstance(node, ast.Name):
            assert node.id not in banned, f"scheduler references {node.id}"
        elif isinstance(node, ast.Attribute):
            assert node.attr not in banned, f"scheduler references {node.attr}"


def test_allocator_stress_many_frees():
    """Freeing thousands of pages must be cheap (the double-free guard is
    set-backed, not an O(n) list scan per page) and exact: every page
    returns, LIFO reuse order holds, and misuse still raises."""
    n = 4097
    alloc = PageAllocator(n)
    rng = np.random.default_rng(0)
    held = [alloc.alloc(64) for _ in range(64)]    # drain the pool
    assert alloc.n_free == 0
    order = rng.permutation(len(held))
    for i in order:
        alloc.free(held[i])
    assert alloc.n_free == alloc.capacity == n - 1
    assert sorted(p for chunk in held for p in chunk) == list(range(1, n))
    again = alloc.alloc(n - 1)
    assert sorted(again) == list(range(1, n))
    alloc.free(again)
    with pytest.raises(ValueError):
        alloc.free([again[0]])                      # double free
    with pytest.raises(ValueError):
        alloc.free([n + 5])                         # out of range
    with pytest.raises(ValueError):
        alloc.free([0])                             # scratch page
    probe = alloc.alloc(2)
    with pytest.raises(ValueError):
        alloc.free([probe[0], probe[0]])            # intra-batch duplicate
    assert alloc.n_free == alloc.capacity - 2       # failed frees change nothing


def test_block_table_array_rejects_truncation():
    """A block table narrower than a sequence's page list must raise —
    silently dropping live pages from the kernel's walk would corrupt
    generation with no visible failure."""
    from repro.serve.engine.pages import PagedKVCache

    kv = PagedKVCache({}, n_pages=16, page_size=4)
    kv.open(0)
    kv.ensure(0, 11)                                # 3 pages
    with pytest.raises(ValueError):
        kv.block_table_array([0], 2)
    bt = kv.block_table_array([0, None], 4)         # padding is fine
    assert bt.shape == (2, 4)
    assert int(bt[0, 3]) == 0 and int(bt[1, 0]) == 0


def test_allocator_rejects_double_free_and_oversize():
    alloc = PageAllocator(5)
    pages = alloc.alloc(3)
    alloc.free(pages)
    with pytest.raises(ValueError):
        alloc.free([pages[0], pages[0]])
    with pytest.raises(MemoryError):
        alloc.alloc(10)
    assert pages_for(17, 8) == 3


def test_oversized_request_rejected(stack):
    adapter = _adapter(stack, "bf16")
    eng = ServeEngine(adapter, n_pages=3, page_size=4)
    with pytest.raises(ValueError):
        eng.submit(EngineRequest(rid=0, prompt=list(range(32)),
                                 sampling=SamplingParams(max_new=8)))
    with pytest.raises(ValueError):
        eng.submit(EngineRequest(rid=1, prompt=[1, 2],
                                 sampling=SamplingParams(max_new=0)))
    stale = EngineRequest(rid=2, prompt=[1, 2])
    stale.n_cached = 3
    with pytest.raises(ValueError):
        eng.submit(stale)


# ----------------------------------------------------------------------
# generalized state model: ssm / hybrid / moe families through the same
# scheduler (kv pages, register slots, or both, per the adapter's spec)
# ----------------------------------------------------------------------

FAMILY_ARCHS = ["mamba2-1.3b", "zamba2-1.2b", "deepseek-moe-16b"]


@pytest.fixture(scope="module")
def family_stack():
    """One (cfg, model, params, adapter) per non-dense family. MoE runs
    through the dense oracle (per-token exact → chunking-invariant): the
    capacity-bounded dispatch's drops depend on chunk length, so gather
    dispatch cannot satisfy a chunked≡whole-prompt parity contract."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch).reduced()
            kw = {"moe_dense_oracle": True} if cfg.uses_moe else {}
            model = build_model(cfg, **kw)
            params = model.init(jax.random.PRNGKey(1))
            cache[arch] = (cfg, model, params,
                           as_servable(model, params, cache_dtype=jnp.float32))
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_engine_matches_dense_path_families(family_stack, arch):
    """Acceptance: the paged engine serves ssm (register slots only),
    hybrid (kv pages + register slots), and moe (kv pages + routed FFN)
    smoke configs with the same greedy tokens and logits as the
    dense-cache path."""
    cfg, model, params, adapter = family_stack(arch)
    spec = adapter.state_spec
    assert spec.kv == (cfg.family != "ssm")
    assert spec.register == (cfg.family in ("ssm", "hybrid"))
    _, done = _engine_run(adapter, PROMPTS, n_pages=65)
    for rid, prompt in enumerate(PROMPTS):
        want_toks, want_logits = _dense_greedy(adapter, prompt, MAX_NEW)
        req = done[rid]
        assert req.generated == want_toks, (rid, req.generated, want_toks)
        for got, want in zip(req.step_logits, want_logits):
            assert np.corrcoef(got, want)[0, 1] >= 0.999


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_chunked_prefill_matches_stepwise_families(family_stack, arch):
    """Chunked prefill ≡ one-token-at-a-time prefill for the new
    families: carried SSM state across padded chunk boundaries must be
    exact (valid_len masking), not just close."""
    _, _, _, adapter = family_stack(arch)
    _, chunked = _engine_run(adapter, PROMPTS, n_pages=65, prefill_chunk=4)
    _, stepwise = _engine_run(adapter, PROMPTS, n_pages=65, prefill_chunk=1)
    for rid in range(len(PROMPTS)):
        assert chunked[rid].generated == stepwise[rid].generated
        for a, b in zip(chunked[rid].step_logits, stepwise[rid].step_logits):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "zamba2-1.2b"])
def test_register_slot_leak_accounting(family_stack, arch):
    """admit → finish → readmit: every register slot returns to the free
    list, a recycled slot is reused for the next admission, and scrubbing
    on release means it cannot observe its predecessor's state (all
    non-scratch slot rows are zero between runs — satellite bugfix)."""
    _, _, _, adapter = family_stack(arch)
    eng, _ = _engine_run(adapter, PROMPTS, n_pages=65, max_seqs=2)
    regs = eng.kv.registers
    assert regs is not None
    assert regs.n_free == regs.capacity == 2
    assert not eng.kv.slots
    # scrub-on-release: every allocatable slot (and, for hybrid, every
    # freed kv page) holds zeros — a recycled slot/page cannot leak
    for leaf in jax.tree.leaves(eng.kv.state["register"]):
        assert bool(jnp.all(leaf[:, 1:] == 0)), "stale register state"
    for leaf in jax.tree.leaves(eng.kv.state["kv"]):
        assert bool(jnp.all(leaf[:, 1:] == 0)), "stale kv pages"

    # readmission reuses the freed slot and sees zeroed state
    used_before = set(range(1, regs.n_slots)) - set(regs._free)
    assert not used_before
    eng.submit(EngineRequest(rid=99, prompt=[5, 6, 7],
                             sampling=SamplingParams(max_new=2)))
    eng.step()
    assert eng.kv.slots[99] in range(1, regs.n_slots)
    while eng.queue or eng.active:
        eng.step()
    assert regs.n_free == regs.capacity


def test_moe_capacity_path_serves_end_to_end():
    """The real capacity-bounded gather dispatch (no oracle) must serve
    through the engine too — no parity contract (drops are
    chunk-length-dependent by design), but generation completes with
    finite logits and clean page accounting."""
    cfg = get_config("deepseek-moe-16b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    adapter = as_servable(model, params)
    eng, done = _engine_run(adapter, PROMPTS, n_pages=65)
    for rid in range(len(PROMPTS)):
        assert len(done[rid].generated) == MAX_NEW
        assert all(np.isfinite(lg).all() for lg in done[rid].step_logits)
    assert eng.kv.allocator.n_free == eng.kv.allocator.capacity


@pytest.mark.parametrize("arch,match", [
    ("hubert-xlarge", "encoder"),        # no autoregressive decode
    ("internvl2-2b", "frontend"),        # non-token inputs
])
def test_adapter_rejects_unservable_families(arch, match):
    """Capability check regression: genuinely unservable configs fail at
    adapter construction with a clear error, not deep inside the engine."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match=match):
        as_servable(model, params)


def test_engine_respects_use_kernels_scope(stack):
    """The fused phase jits must compile once per kernels-enabled state
    (like `QuantizedDenseLM._jitted`), so dispatched-vs-reference
    comparisons through the engine are real — and bit-identical, since
    both paths compute the same arithmetic."""
    from repro.kernels import ops as kops

    adapter = _adapter(stack, "int_kv8")
    runs = {}
    for enabled in (True, False):
        with kops.use_kernels(enabled):
            _, done = _engine_run(adapter, PROMPTS[:1])
        runs[enabled] = done[0]
    assert runs[True].generated == runs[False].generated
    for a, b in zip(runs[True].step_logits, runs[False].step_logits):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# robustness satellites: admission bookkeeping + submit-time validation
# ----------------------------------------------------------------------

@pytest.mark.parametrize("admission", ["reserve", "optimistic"])
def test_committed_total_matches_sum(stack, admission):
    """The O(n) admission bookkeeping: the running `_committed_total`
    equals `sum(_committed.values())` after every step under both
    policies (admit, growth, finish all update it), and drains to zero."""
    adapter = _adapter(stack, "bf16")
    prompts = [[(7 * i + j) % 500 for j in range(3 + i % 4)]
               for i in range(8)]
    eng = ServeEngine(adapter, n_pages=5, page_size=8, max_seqs=2,
                      prefill_chunk=4, admission=admission)
    for rid, p in enumerate(prompts):
        eng.submit(EngineRequest(rid=rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=3)))
    done = []
    while eng.queue or eng.active:
        done.extend(eng.step())
        assert eng._committed_total == sum(eng._committed.values())
        eng.check_books()
    assert len(done) == len(prompts)
    assert eng._committed_total == 0 and not eng._committed
    assert eng.kv.allocator.n_free == eng.kv.allocator.capacity


def test_both_admission_policies_same_tokens(stack):
    """With an ample pool, optimistic and reserve admission produce
    bit-identical generations — the policy only changes *when* requests
    are admitted, never what they generate."""
    adapter = _adapter(stack, "bf16")
    runs = {}
    for mode in ("reserve", "optimistic"):
        _, done = _engine_run(adapter, PROMPTS, admission=mode)
        runs[mode] = {r: done[r].generated for r in done}
    assert runs["reserve"] == runs["optimistic"]


def test_submit_rejects_over_context_window(stack, family_stack):
    """Satellite: prompt + max_new beyond the model context window is
    rejected at submit with a clear error — for kv specs (where the pool
    implies a bound) AND register-only specs (which reserve 0 pages and
    previously sailed through to fail deep inside prefill)."""
    adapter = _adapter(stack, "bf16")
    # kv spec, explicit window
    eng = ServeEngine(adapter, n_pages=33, page_size=8, max_context=16)
    with pytest.raises(ValueError, match="context window"):
        eng.submit(EngineRequest(rid=0, prompt=list(range(12)),
                                 sampling=SamplingParams(max_new=8)))
    # kv spec, implied window = capacity · page_size (32 · 8 = 256)
    assert eng.max_context == 16
    eng2 = ServeEngine(adapter, n_pages=33, page_size=8)
    assert eng2.max_context == 32 * 8

    # register-only spec: no pool-implied bound, but an explicit window
    # must still reject at submit
    _, _, _, ssm_adapter = family_stack("mamba2-1.3b")
    assert not ssm_adapter.state_spec.kv
    eng3 = ServeEngine(ssm_adapter, n_pages=5, page_size=8, max_context=10)
    with pytest.raises(ValueError, match="context window"):
        eng3.submit(EngineRequest(rid=0, prompt=list(range(8)),
                                  sampling=SamplingParams(max_new=8)))
    eng4 = ServeEngine(ssm_adapter, n_pages=5, page_size=8)
    assert eng4.max_context is None     # register state never grows


def test_optimistic_submit_rejects_never_admittable(stack):
    """A prompt whose pages can never fit beside the headroom watermark
    is rejected at submit instead of stalling the queue forever."""
    adapter = _adapter(stack, "bf16")
    eng = ServeEngine(adapter, n_pages=5, page_size=4, max_seqs=2,
                      admission="optimistic", headroom_pages=2)
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(EngineRequest(rid=0, prompt=list(range(12)),
                                 sampling=SamplingParams(max_new=2)))


def test_on_token_streams_at_step_boundaries(stack):
    """`submit(req, on_token=...)` delivers every generated token exactly
    once, in order, at the boundary of the step that produced it — and a
    request without a callback costs nothing."""
    from repro.serve.engine import ServeEngine as _SE
    adapter = _adapter(stack, "bf16")
    eng = _SE(adapter, n_pages=33, page_size=8, max_seqs=2,
              prefill_chunk=4)
    streamed: dict[int, list[int]] = {0: [], 2: []}
    for rid, p in enumerate(PROMPTS):
        cb = (lambda r, t: streamed[r].append(t)) if rid in streamed \
            else None
        eng.submit(EngineRequest(rid=rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=MAX_NEW)),
                   on_token=cb)
    done = {}
    while eng.queue or eng.active:
        for r in eng.step():
            done[r.rid] = r
        # boundary contract: after each step, everything generated so
        # far has been delivered — no buffering across steps
        for req in eng.active:
            if req.rid in streamed:
                assert streamed[req.rid] == req.generated
    for rid in streamed:
        assert streamed[rid] == done[rid].generated
        assert len(streamed[rid]) == MAX_NEW


class _PoisonAdapter:
    """Delegating adapter that returns NaN logits for any row whose true
    context length equals `poison_len` — a deterministic stand-in for
    numerically-poisoned model output (overflowed activation, corrupted
    weight). Everything else passes straight through to the inner
    adapter, so other rows of the same fused dispatch are untouched."""

    def __init__(self, inner, poison_len):
        self._inner = inner
        self._poison_len = poison_len

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def forward_chunk(self, params, tokens, state, pos, bt=None, lens=None,
                      reg=None, **kw):
        out = self._inner.forward_chunk(params, tokens, state, pos, bt,
                                        lens, reg, **kw)
        logits = jnp.where((lens == self._poison_len)[:, None, None],
                           jnp.nan, out[0])
        return (logits,) + tuple(out[1:])


def test_poisoned_row_fails_without_perturbing_batch(stack):
    """Satellite: a non-finite logits row terminates exactly that request
    (`outcome="failed"`, counted `engine.requests.poisoned`) instead of
    entering a garbage token into its stream or crashing the step; the
    other rows of the same fused dispatches stay bit-identical."""
    adapter = _adapter(stack, "bf16")
    _, base = _engine_run(adapter, PROMPTS, max_new=5)
    # decode lens = n_cached + 1, so rid 2 (prompt 7) spans 8..11 while
    # rid 0 (prompt 5) tops out at 9 and rid 1 (prompt 3) at 7 — length
    # 10 poisons exactly rid 2's 4th-generated-token dispatch, mid-decode
    eng, done = _engine_run(_PoisonAdapter(adapter, poison_len=10),
                            PROMPTS, max_new=5)
    assert done[2].outcome == "failed"
    assert "non-finite logits" in done[2].failed
    # tokens generated before the poison are the baseline's, and the
    # poisoned sample itself never entered the stream
    assert done[2].generated == base[2].generated[:3]
    assert eng.metrics.counter("engine.requests.poisoned").value == 1
    assert eng.metrics.counter("engine.requests.failed").value == 1
    for rid in (0, 1):
        assert done[rid].outcome == "length"
        assert done[rid].generated == base[rid].generated, rid


def test_drain_finishes_inflight_rejects_new(stack):
    """Satellite: drain() stops admission (never-admitted queue entries
    cancel), finishes all in-flight work, asserts every pool empty, and
    rejects subsequent submits."""
    adapter = _adapter(stack, "bf16")
    _, base = _engine_run(adapter, PROMPTS)
    eng = ServeEngine(adapter, n_pages=33, page_size=8, max_seqs=2,
                      prefill_chunk=4)
    for rid, p in enumerate(PROMPTS):
        eng.submit(EngineRequest(rid=rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=MAX_NEW)))
    done = eng.step()                 # rid 0/1 admitted; rid 2 queued
    done += eng.drain()
    by_rid = {r.rid: r for r in done}
    assert len(by_rid) == len(PROMPTS)
    assert by_rid[2].outcome == "cancelled" and not by_rid[2].generated
    for rid in (0, 1):
        assert by_rid[rid].outcome == "length"
        assert by_rid[rid].generated == base[rid].generated
    with pytest.raises(RuntimeError, match="draining"):
        eng.submit(EngineRequest(rid=9, prompt=[1],
                                 sampling=SamplingParams(max_new=1)))


def test_stream_callback_error_isolated(stack):
    """Satellite: a raising on_token callback is counted and dropped —
    it cannot abort the step or starve the other streams, which still
    deliver every token exactly once."""
    adapter = _adapter(stack, "bf16")
    eng = ServeEngine(adapter, n_pages=33, page_size=8, max_seqs=2,
                      prefill_chunk=4)
    got: list[int] = []

    def bad(rid, tok):
        raise RuntimeError("consumer died")

    for rid, p in enumerate(PROMPTS):
        cb = {0: bad, 1: lambda r, t: got.append(t)}.get(rid)
        eng.submit(EngineRequest(rid=rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=MAX_NEW)),
                   on_token=cb)
    done = {r.rid: r for r in eng.run()}
    assert len(done) == len(PROMPTS)
    assert all(r.outcome == "length" for r in done.values())
    # the broken consumer was dropped after its first raise
    assert eng.metrics.counter("engine.stream.callback_errors").value == 1
    assert 0 not in eng._callbacks
    # the healthy stream delivered everything exactly once, in order
    assert got == done[1].generated


def test_release_scrubs_in_one_fused_dispatch(stack):
    """Satellite: each request release batches its scrub into exactly ONE
    fused dispatch (tallied as `scrub_state` in the kernels.ops counts),
    regardless of how many pages it frees."""
    from repro.kernels import ops as kops

    def scrubs():
        return sum(v for (entry, _), v in kops.dispatch_counts().items()
                   if entry == "scrub_state")

    adapter = _adapter(stack, "bf16")
    eng, done = _engine_run(adapter, PROMPTS)
    assert len(done) == len(PROMPTS)
    kops.reset_dispatch_counts()
    eng2, _ = _engine_run(adapter, PROMPTS)
    # fault-free run, no sharing: one release — one scrub — per request
    assert scrubs() == len(PROMPTS)
    assert eng2.kv.pages_scrubbed >= len(PROMPTS)
