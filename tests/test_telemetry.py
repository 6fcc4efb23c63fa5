"""Telemetry: metrics primitives, trace well-formedness, schema
validation, and — the acceptance-critical part — bit-path neutrality:
turning tracing and quality probes on must not change a single generated
token or logit on any serving path.
"""
import importlib.util
import json
import math
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.transformer import build_model
from repro.serve.engine import (EngineRequest, SamplingParams, ServeEngine,
                                as_servable)
from repro.serve.quantized import QuantizedDenseLM, pack_dense_params
from repro.serve.telemetry import (PID_ENGINE, PROBE_STATS, SCHEMA_VERSION,
                                   Histogram, MetricsRegistry, QualityProbes,
                                   Tracer, validate_snapshot, validate_trace)
from repro.serve.telemetry.metrics import Counter

ROOT = pathlib.Path(__file__).resolve().parents[1]

PROMPTS = [[3, 14, 15, 92, 6], [53, 58, 9], [7, 9, 3, 23, 84, 62]]
MAX_NEW = 3


@pytest.fixture(scope="module")
def stack():
    """bf16 + packed-int4 adapters over one tiny dense config (no PTQ:
    the telemetry tests need the serving paths, not quantizer quality)."""
    cfg = get_config("llama3-1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    packed = pack_dense_params(params, cfg)
    return cfg, model, params, packed


def _run(adapter, *, prompts=PROMPTS, **kw):
    kw.setdefault("n_pages", 33)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seqs", 2)
    kw.setdefault("prefill_chunk", 4)
    eng = ServeEngine(adapter, record_logits=True, **kw)
    for rid, p in enumerate(prompts):
        eng.submit(EngineRequest(rid=rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=MAX_NEW)))
    done = {r.rid: r for r in eng.run()}
    assert len(done) == len(prompts)
    return eng, done


# ----------------------------------------------------------------------
# metrics primitives
# ----------------------------------------------------------------------

def test_histogram_bucket_boundaries():
    h = Histogram(base=1e-6, growth=2.0, n_buckets=40)
    # bucket 0 = [0, base), bucket i = [base·g^(i-1), base·g^i)
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(0.999e-6) == 0
    assert h.bucket_index(1e-6) == 1          # boundary is inclusive below
    assert h.bucket_index(1.999e-6) == 1
    assert h.bucket_index(2e-6) == 2
    assert h.bucket_index(4e-6) == 3
    assert h.bucket_index(1e12) == 39         # open-ended last bucket
    assert h.upper(0) == h.lower(1) == 1e-6
    assert h.upper(3) == h.lower(4) == 8e-6
    assert math.isinf(h.upper(39))
    for i in range(1, 39):                    # boundaries classify exactly
        assert h.bucket_index(h.lower(i)) == i


def _check_quantile_property(h, vals, q):
    """The estimate must land in the bucket holding the nearest-rank
    sample (so it is within one growth factor of the exact statistic) and
    inside the observed [min, max]."""
    est = h.quantile(q)
    rank = max(1, math.ceil(q * len(vals)))
    sample = sorted(vals)[rank - 1]
    b = h.bucket_index(sample)
    assert h.lower(b) <= est <= min(h.upper(b), max(vals))
    assert min(vals) <= est <= max(vals)


def test_quantile_within_bucket_of_nearest_rank():
    rng = np.random.default_rng(0)
    for _ in range(20):
        n = int(rng.integers(1, 200))
        vals = np.exp(rng.normal(-8, 4, size=n)).tolist()  # µs..hours
        h = Histogram()
        for v in vals:
            h.observe(v)
        for q in (0.0, 0.5, 0.95, 0.99, 1.0):
            _check_quantile_property(h, vals, q)


def test_quantile_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.lists(st.floats(min_value=0.0, max_value=1e9,
                                  allow_nan=False), min_size=1,
                        max_size=100),
               st.sampled_from([0.5, 0.9, 0.95, 0.99]))
    @hyp.settings(deadline=None, max_examples=200)
    def check(vals, q):
        h = Histogram()
        for v in vals:
            h.observe(v)
        _check_quantile_property(h, vals, q)

    check()


def test_histogram_merge_is_exact():
    a, b, both = Histogram(), Histogram(), Histogram()
    rng = np.random.default_rng(1)
    va, vb = rng.exponential(1e-3, 50), rng.exponential(10.0, 70)
    for v in va:
        a.observe(v)
        both.observe(v)
    for v in vb:
        b.observe(v)
        both.observe(v)
    a.merge(b)
    assert a.counts == both.counts and a.count == both.count
    assert a.min == both.min and a.max == both.max
    assert a.sum == pytest.approx(both.sum)
    with pytest.raises(ValueError):
        a.merge(Histogram(base=1e-3))          # config mismatch


def test_counter_monotonic_and_registry_reset():
    reg = MetricsRegistry()
    c = reg.counter("engine.steps")
    with pytest.raises(ValueError):
        c.inc(-1)
    c.inc(3)
    h = reg.histogram("engine.step.wall_s")
    h.observe(0.5)
    reg.reset()
    # identity survives the reset (hot-loop callers hold the instrument)
    assert reg.counter("engine.steps") is c and c.value == 0
    assert h.count == 0 and math.isinf(h.min)


def test_registry_merge_multi_host():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("engine.steps").inc(2)
    b.counter("engine.steps").inc(5)
    b.gauge("engine.queue.depth").set(7)
    b.histogram("engine.step.wall_s").observe(1.0)
    a.merge(b)
    assert a.counter("engine.steps").value == 7
    assert a.gauge("engine.queue.depth").value == 7
    assert a.histogram("engine.step.wall_s").count == 1


# ----------------------------------------------------------------------
# trace well-formedness
# ----------------------------------------------------------------------

def test_tracer_emits_valid_chrome_trace(tmp_path):
    tr = Tracer()
    tr.begin("request", pid=2, tid=1)
    tr.begin("queued", pid=2, tid=1)
    tr.end("queued", pid=2, tid=1)
    tr.instant("alloc_pages", pid=2, tid=1, args={"pages": 2})
    with tr.span("engine.step", args={"step_num": 0}):
        pass
    tr.end("request", pid=2, tid=1)
    n = validate_trace(tr.to_dict())
    assert n == len(tr.events)
    path = tmp_path / "t.json"
    tr.save(str(path))
    with open(path) as f:
        obj = json.load(f)                    # round-trips as plain JSON
    assert validate_trace(obj) == n
    # ts are µs on one monotonic clock: B before E for every span
    evs = [e for e in obj["traceEvents"] if e["ph"] in "BE"]
    assert evs[0]["ts"] <= evs[-1]["ts"]


@pytest.mark.parametrize("events,msg", [
    ([{"name": "a", "ph": "E", "ts": 1.0, "pid": 1, "tid": 0}],
     "without an open"),
    ([{"name": "a", "ph": "B", "ts": 1.0, "pid": 1, "tid": 0},
      {"name": "b", "ph": "E", "ts": 2.0, "pid": 1, "tid": 0}],
     "must nest"),
    ([{"name": "a", "ph": "B", "ts": 1.0, "pid": 1, "tid": 0}],
     "unclosed"),
    ([{"name": "a", "ph": "X", "ts": 1.0, "dur": -5, "pid": 1, "tid": 0}],
     "invalid dur"),
    ([{"name": "a", "ph": "B", "ts": -1.0, "pid": 1, "tid": 0}],
     "invalid ts"),
    ([{"ph": "B", "ts": 1.0, "pid": 1, "tid": 0}], "missing 'name'"),
])
def test_validate_trace_rejects_malformed(events, msg):
    with pytest.raises(ValueError, match=msg):
        validate_trace({"traceEvents": events})


# ----------------------------------------------------------------------
# schema validation
# ----------------------------------------------------------------------

def test_snapshot_schema_rejects_unknown_and_missing(stack):
    cfg, model, params, _ = stack
    eng, _ = _run(as_servable(model, params))
    snap = eng.metrics_snapshot()
    validate_snapshot(snap)

    bad = json.loads(json.dumps(snap))
    bad["counters"]["engine.typo_metric"] = 1
    with pytest.raises(ValueError, match="unknown counter"):
        validate_snapshot(bad)

    bad = json.loads(json.dumps(snap))
    del bad["histograms"]["engine.step.wall_s"]
    with pytest.raises(ValueError, match="missing histogram"):
        validate_snapshot(bad)

    bad = json.loads(json.dumps(snap))
    bad["schema_version"] = SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema_version"):
        validate_snapshot(bad)

    bad = json.loads(json.dumps(snap))
    bad["histograms"]["engine.step.wall_s"]["count"] += 1
    with pytest.raises(ValueError, match="inconsistent"):
        validate_snapshot(bad)


# ----------------------------------------------------------------------
# engine integration
# ----------------------------------------------------------------------

def test_engine_snapshot_and_trace_valid(stack):
    cfg, model, params, _ = stack
    tr = Tracer()
    eng, done = _run(as_servable(model, params), tracer=tr)
    snap = eng.metrics_snapshot()
    validate_snapshot(snap)
    validate_trace(tr.to_dict())
    c = snap["counters"]
    assert c["engine.requests.submitted"] == len(PROMPTS)
    assert c["engine.requests.finished"] == len(PROMPTS)
    assert c["engine.generated_tokens"] \
        == sum(len(r.generated) for r in done.values()) \
        == len(PROMPTS) * MAX_NEW
    assert c["engine.prefill_tokens"] == sum(len(p) for p in PROMPTS)
    assert 0 < c["engine.pages_walked"] < c["engine.pages_walked_dense"]
    # back-compat property views read the same registry counters
    assert eng.n_steps == c["engine.steps"] > 0
    assert eng.pages_walked == c["engine.pages_walked"]
    g = snap["gauges"]
    assert g["engine.pages.in_use"] == 0           # all released
    assert g["engine.pages.peak_in_use"] > 0
    assert g["engine.pages.scrubbed"] > 0
    h = snap["histograms"]
    assert h["engine.step.wall_s"]["count"] == c["engine.steps"]
    assert h["engine.decode.token_latency_s"]["count"] \
        == c["engine.generated_tokens"]
    assert h["engine.request.e2e_s"]["count"] == len(PROMPTS)
    # the kernel dispatch tallies were mirrored in
    assert any(k.startswith("kernels.dispatch.") for k in c)
    assert c["engine.dispatch.new_shapes"] > 0
    assert h["engine.step.host_s"]["count"] == c["engine.steps"]
    assert h["engine.step.host_s"]["sum"] < h["engine.step.wall_s"]["sum"]
    # the engine track holds every step's phases as spans numbered by
    # step, with no device-synchronised dispatch timing left; every
    # request left a lifecycle
    evs = tr.to_dict()["traceEvents"]
    assert not any(e["ph"] == "X" for e in evs)
    phases = [e for e in evs if e["ph"] == "B" and e["pid"] == PID_ENGINE]
    steps = [e["args"]["step_num"] for e in phases
             if e["name"] == "engine.step"]
    assert steps == list(range(c["engine.steps"]))
    assert {e["name"] for e in phases} == {
        "engine.step", "engine.admit", "engine.account", "engine.stream"} | {
        f"engine.{p}.{ph}" for p in ("prepare", "dispatch", "wait", "commit")
        for ph in ("decode", "prefill")}
    for e in phases:
        if e["name"].startswith("engine.dispatch."):
            assert {"n_cols", "new_shape"} <= set(e["args"])
    assert sum(e["ph"] == "B" and e["name"] == "request" for e in evs) \
        == len(PROMPTS)


def test_tracing_is_bit_path_neutral(stack, tmp_path):
    """Same tokens AND bit-identical recorded logits with tracing on, and
    with the profiler recording the engine's step spans too."""
    cfg, model, params, _ = stack
    _, plain = _run(as_servable(model, params))
    _, traced = _run(as_servable(model, params), tracer=Tracer())
    with jax.profiler.trace(str(tmp_path)):
        _, profiled = _run(as_servable(model, params), tracer=Tracer())
    for run in (traced, profiled):
        for rid in plain:
            assert run[rid].generated == plain[rid].generated
            for a, b in zip(run[rid].step_logits, plain[rid].step_logits):
                assert np.array_equal(a, b)


def test_probes_are_bit_path_neutral_int4(stack):
    """The probe variant of the fused forward (barrier-isolated side
    computation) must not perturb the integer path: greedy tokens and
    logits bit-identical, and the per-layer quality stats land in the
    registry."""
    cfg, model, params, packed = stack
    qlm = QuantizedDenseLM(cfg, block_size=16)
    _, plain = _run(as_servable(qlm, packed))
    probes = QualityProbes(every_k=2)
    eng, probed = _run(as_servable(qlm, packed), quality_probes=probes,
                       tracer=Tracer())
    for rid in plain:
        assert probed[rid].generated == plain[rid].generated
        for a, b in zip(probed[rid].step_logits, plain[rid].step_logits):
            assert np.array_equal(a, b)
    snap = eng.metrics_snapshot()
    validate_snapshot(snap)
    n_probed = snap["counters"]["quality.probe_dispatches"]
    assert n_probed > 0
    for stat in PROBE_STATS:
        h = snap["histograms"][f"quality.{stat}"]
        assert h["count"] == n_probed * cfg.n_layers
        for layer in range(cfg.n_layers):
            assert f"quality.layer{layer:02d}.{stat}" in snap["gauges"]
    # probe physics sanity: imbalance >= 1 by construction, saturation a
    # rate in [0, 1]
    assert snap["histograms"]["quality.l1_imbalance_post"]["min"] >= 1.0
    assert 0.0 <= snap["histograms"]["quality.sat_rate"]["max"] <= 1.0


def _spans():
    """The benchmark's reduction of the engine's profiler spans."""
    bench = str(ROOT / "perfbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from bench import spans, tracefile
    return spans, tracefile


# the phases of one step, in order: a prefill prepare may end the chunk
# loop without a dispatch (its head was preempted)
PHASE_ORDER = re.compile(
    r"engine\.admit"
    r"( engine\.prepare\.decode engine\.dispatch\.decode"
    r" engine\.wait\.decode engine\.commit\.decode)?"
    r"( engine\.prepare\.prefill( engine\.dispatch\.prefill"
    r" engine\.wait\.prefill engine\.commit\.prefill)?)*"
    r" engine\.account engine\.stream")


def test_profiler_step_phases_nest_in_order(stack, tmp_path):
    """Under the profiler (CPU backend) every engine step is one
    `engine.step` span holding its phases in order, inside it, without
    overlap; its `rows` arg is the number of decoding requests."""
    spans, tracefile = _spans()
    cfg, model, params, _ = stack
    eng = ServeEngine(as_servable(model, params), n_pages=33, page_size=8,
                      max_seqs=2, prefill_chunk=4)
    for rid, p in enumerate(PROMPTS):
        eng.submit(EngineRequest(rid=rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=MAX_NEW)))
    decoding = []
    with jax.profiler.trace(str(tmp_path)):
        while eng.queue or eng.active:
            decoding.append(len(eng.decoding))
            eng.step()
    ex = spans.extract(tracefile.find_xplane(tmp_path))
    steps = spans.steps(ex)
    assert [s[3]["step_num"] for s, _ in steps] == list(range(len(decoding)))
    for (step, children), rows in zip(steps, decoding):
        assert step[3]["rows"] == rows
        assert step[3]["chunks"] == sum(
            c[0] == "engine.dispatch.prefill" for c in children)
        seq = " ".join(c[0] for c in children)
        assert PHASE_ORDER.fullmatch(seq), seq
        end = step[1]
        for c in children:
            assert step[1] <= c[1] and c[1] + c[2] <= step[1] + step[2]
            assert c[1] >= end          # no overlap, in start order
            end = c[1] + c[2]
        dispatch = [c for c in children if c[0] == "engine.dispatch.decode"]
        assert [c[3]["rows"] for c in dispatch] == ([rows] if rows else [])
    red = spans.reduce(ex, 1.0)
    assert red["n_steps"] == len(decoding)
    assert red["n_decode_steps"] > 0 and red["host_ms"] > 0


def test_span_reduction_hand_made():
    """Host time is a decode-only step minus its waits; host-bound idle is
    the window outside the union of device ops and wait spans (interval
    intersection); device time is the mean `engine_decode*` module."""
    spans, _ = _spans()
    step = {"step_num": 0, "rows": 2, "chunks": 0}
    ex = {"spans": [
        ["engine.step", 0, 100, step], ["engine.admit", 0, 5, {}],
        ["engine.prepare.decode", 5, 15, {}],
        ["engine.dispatch.decode", 20, 5, {"rows": 2}],
        ["engine.wait.decode", 25, 60, {}],
        ["engine.commit.decode", 85, 5, {}], ["engine.account", 90, 5, {}],
        ["engine.stream", 95, 5, {}],
        ["engine.step", 100, 100, dict(step, step_num=1, chunks=1)],
        ["engine.dispatch.decode", 120, 5, {"rows": 2}],
        ["engine.wait.decode", 125, 20, {}],
        ["engine.dispatch.prefill", 145, 5, {"tokens": 8}],
        ["engine.wait.prefill", 150, 30, {}]],
        "devices": [{"plane": "/device:TPU:0",
                     "ops": [[22, 50], [80, 10], [125, 15], [160, 15]],
                     "modules": [["jit_engine_decode(1)", 22, 68],
                                 ["jit_engine_decode(1)", 125, 15],
                                 ["jit_engine_prefill(2)", 160, 15]]}]}
    r = spans.reduce(ex, 200e-9)
    assert r["n_steps"] == 2 and r["n_decode_steps"] == 1
    assert r["host_ms"] == pytest.approx(40e-6)
    assert r["wait_ms"] == pytest.approx(60e-6)
    assert r["busy_s"] == pytest.approx(90e-9)
    # covered: [22, 90] by ops and the decode wait, [125, 145] and
    # [150, 180] by waits and ops: 68 + 20 + 30 of 200
    assert r["idle_host_s"] == pytest.approx(82e-9)
    assert r["device_ms"] == pytest.approx(41.5e-6)


def test_span_reduction_on_recorded_chip_trace():
    """The reduction pinned on a slice of a `granite-3-2b.batch` trace
    recorded on a TPU v5e (four 64-row decode steps,
    `perfbench/tests/data/engine_trace_sample.json`, made by
    `spans.sample`): the host's own time, the host-bound idle share and
    the decode program's device time, and host-bound idle within the
    device's idle."""
    spans, _ = _spans()
    ex = json.loads((ROOT / "perfbench" / "tests" / "data"
                     / "engine_trace_sample.json").read_text())
    w = ex["window_s"]
    r = spans.reduce(ex, w)
    assert r["n_steps"] == r["n_decode_steps"] == 4
    assert r["host_ms"] == pytest.approx(33.737302)
    assert 100 * r["idle_host_s"] / w == pytest.approx(9.344853884285508)
    assert r["device_ms"] == pytest.approx(315.86269175)
    device_idle = 100 * (1 - r["busy_s"] / w)
    assert device_idle == pytest.approx(10.246706755877166)
    assert 0 < 100 * r["idle_host_s"] / w <= device_idle
    # a step is its host time and its wait, and the decode program runs
    # inside the wait
    assert r["host_ms"] + r["wait_ms"] == pytest.approx(
        1e3 * w / 4, rel=0.01)
    assert r["device_ms"] < r["wait_ms"]


def test_new_shapes_counts_distinct_dispatch_signatures(stack):
    """`engine.dispatch.new_shapes` counts each (program, shape) signature
    once — the number of programs jit traced — and does not grow when the
    same traffic repeats its shapes."""
    cfg, model, params, _ = stack
    eng, _ = _run(as_servable(model, params))

    def traced():
        return sum(fn._cache_size() for fn in eng._jit_cache.values())
    n = eng.metrics_snapshot()["counters"]["engine.dispatch.new_shapes"]
    assert n == traced() > 2
    eng.reset_metrics()
    for rid, p in enumerate(PROMPTS):
        eng.submit(EngineRequest(rid=100 + rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=MAX_NEW)))
    eng.run()
    assert eng.metrics_snapshot()["counters"][
        "engine.dispatch.new_shapes"] == 0
    assert traced() == n


def test_fused_programs_are_named(stack, monkeypatch):
    """The lowered decode and prefill programs are modules named
    `jit_engine_decode` / `jit_engine_prefill`, not `jit(wrapped)`."""
    cfg, model, params, _ = stack
    fused = ServeEngine._fused
    texts = {}

    def spy_fused(self, name, impl, variant, shape):
        fn, new = fused(self, name, impl, variant, shape)

        def spy(*args):
            texts.setdefault(name, fn.lower(*args).as_text())
            return fn(*args)
        return spy, new
    monkeypatch.setattr(ServeEngine, "_fused", spy_fused)
    _run(as_servable(model, params), prompts=PROMPTS[:1])
    assert "module @jit_engine_decode " in texts["decode"]
    assert "module @jit_engine_prefill " in texts["prefill"]


def test_probes_rejected_on_dense_adapter(stack):
    cfg, model, params, _ = stack
    with pytest.raises(ValueError, match="quality probes"):
        ServeEngine(as_servable(model, params), n_pages=33, page_size=8,
                    quality_probes=QualityProbes())


def test_reset_metrics_gives_fresh_window(stack):
    """A second run() on the same engine must not accumulate counters
    across runs once reset_metrics() marks the window boundary."""
    cfg, model, params, _ = stack
    eng, _ = _run(as_servable(model, params))
    first = eng.metrics_snapshot()["counters"]
    eng.reset_metrics()
    zero = eng.metrics_snapshot()
    validate_snapshot(zero)                  # still schema-complete
    assert zero["counters"]["engine.steps"] == 0
    assert zero["gauges"]["engine.pages.peak_in_use"] == 0
    for rid, p in enumerate(PROMPTS):
        eng.submit(EngineRequest(rid=100 + rid, prompt=list(p),
                                 sampling=SamplingParams(max_new=MAX_NEW)))
    eng.run()
    second = eng.metrics_snapshot()["counters"]
    for name in ("engine.steps", "engine.prefill_tokens",
                 "engine.decode_tokens", "engine.generated_tokens",
                 "engine.pages_walked", "engine.requests.finished"):
        assert second[name] == first[name], name


def test_register_slot_gauges_on_ssm(stack):
    cfg = get_config("mamba2-1.3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng, _ = _run(as_servable(model, params), prompts=PROMPTS[:1])
    snap = eng.metrics_snapshot()
    validate_snapshot(snap)
    g = snap["gauges"]
    assert g["engine.register_slots.capacity"] == eng.max_seqs
    assert g["engine.register_slots.peak_in_use"] == 1
    assert g["engine.register_slots.scrubbed"] == 1


# ----------------------------------------------------------------------
# bench row schema checks
# ----------------------------------------------------------------------

def _load_bench(name):
    root = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
    sys.path.insert(0, str(root))
    try:
        spec = importlib.util.spec_from_file_location(name,
                                                      root / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(str(root))


def test_kernel_bench_row_schema():
    kb = _load_bench("kernel_bench")
    good = {"op": "decode_ref", "decode_step_us": 12}
    kb._check_schema([good])
    with pytest.raises(ValueError, match="missing required field"):
        kb._check_schema([{"op": "decode_ref"}])
    with pytest.raises(ValueError, match="unknown op family"):
        kb._check_schema([{"op": "mystery_op", "value": 1}])
    with pytest.raises(ValueError, match="missing 'op'"):
        kb._check_schema([{"decode_step_us": 12}])
    kb._check_schema([{"op": "paged_attention_early_exit", "ctx": 64,
                       "kv_heads": 2, "q_heads": 4, "kv_splits": 1,
                       "page_size": 16, "batch": 4, "pages_per_step": 10,
                       "us_per_step": 1.0}])


def test_serve_bench_row_schema():
    sb = _load_bench("serve_bench")
    sb._check_schema([{"path": "x", "family": "dense", "tokens_per_s": 1}])
    with pytest.raises(ValueError, match="missing required field"):
        sb._check_schema([{"path": "x", "family": "dense"}])
