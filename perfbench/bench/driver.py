"""The system under test, driven through its public API: build the engine
from a configuration file, warm every shape the cell's traffic reaches,
then offer the traffic and record what the client sees.

Client-side clocks: a request's scheduled arrival, its submission, its
admission (`EngineRequest.t_admit`) and each token's delivery through the
`on_token` stream. Per step: its host wall time, and every request's
`n_cached` before and after, from which the work model counts prefill
rows, decode rows and dispatches.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from . import spec, warmup
from .traffic import Req

sys.path.insert(0, str(spec.ROOT / "src"))


def _program():
    """The program's public serving API (imported late: a checkout without
    the program must fail here, not at import)."""
    from repro.serve.engine import EngineRequest, SamplingParams, ServeEngine
    return EngineRequest, SamplingParams, ServeEngine


class CompileTally:
    """Compile work seen through jax.monitoring: jit traces, lowering and
    backend-compile seconds, persistent-cache hits and misses."""

    def __init__(self):
        import collections

        import jax
        self.n, self.s = collections.Counter(), collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        self.n[event.rsplit("/", 1)[-1]] += 1

    def _duration(self, event, dur, **_kw):
        key = event.rsplit("/", 1)[-1]
        self.n[key] += 1
        self.s[key] += dur

    def summary(self) -> str:
        n, s = self.n, self.s
        return (f"{n['jaxpr_trace_duration']} jit traces, lowering "
                f"{s['jaxpr_to_mlir_module_duration']:.1f}s, "
                f"{n['backend_compile_duration']} backend compiles "
                f"{s['backend_compile_duration']:.1f}s, persistent cache "
                f"{n['cache_hits']} hits {n['cache_misses']} misses")


def use_checkout_cache(root=spec.ROOT) -> str:
    """JAX's persistent compilation cache at the fixed `<checkout>/.jax_cache`,
    whatever the environment says, handed to the program's own helper."""
    import os

    import jax
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    from repro.launch.compile_cache import enable_compile_cache
    return enable_compile_cache()


def build_engine(config: dict, servable, seed: int):
    """The engine over a model module's `servable`, as the configuration's
    `engine` section sets it."""
    _, _, ServeEngine = _program()
    e = config["engine"]
    return ServeEngine(
        servable, n_pages=e["n_pages"],
        page_size=e["page_size"], max_seqs=e["max_seqs"],
        prefill_chunk=e["prefill_chunk"], token_budget=e["token_budget"],
        seed=int(seed) % (2 ** 31), admission=e["admission"],
        max_context=e["max_context"], prefix_cache=e["prefix_cache"])


def _sampling(mix: dict, greedy: bool, max_new: int):
    _, SamplingParams, _ = _program()
    s = mix["sampling"]
    return SamplingParams(temperature=0.0 if greedy else s["temperature"],
                          max_new=max_new, top_p=s["top_p"])


WARM_RID = 1 << 30


def run_warmup(engine, mix: dict, plan: warmup.Plan, vocab: int) -> int:
    """Serve the plan's requests one at a time (each alone in the engine,
    so its chunk is exactly `p` tokens), warm the page scrubs, then drop
    the cached warm-up prefixes. Returns the number of engine steps."""
    EngineRequest, _, _ = _program()
    rng = np.random.default_rng(0)
    half = max(vocab // 2, 1)
    base = rng.integers(0, half, size=plan.base_len).tolist()
    steps = 0

    def serve(rid, prompt, max_new, greedy):
        nonlocal steps
        engine.submit(EngineRequest(
            rid=rid, prompt=prompt,
            sampling=_sampling(mix, greedy, max_new)))
        while engine.queue or engine.active:
            engine.step()
            steps += 1

    greedy = mix["sampling"]["greedy_share"] >= 1.0
    t = time.perf_counter()
    if plan.base_len:
        serve(WARM_RID, base, 1, greedy)
    for i, (p, c, s, max_new) in enumerate(plan.requests):
        fresh = rng.integers(0, half, size=p).tolist()
        fresh[0] = vocab - 1 - (i % half)   # diverges from every cached run
        serve(WARM_RID + 1 + i, base[:s] + fresh, max_new, greedy)
        now = time.perf_counter()
        print(f"warm {i + 1}/{len(plan.requests)} chunk {p} table {c} "
              f"decode {max_new - 1}: {now - t:.1f}s", file=sys.stderr,
              flush=True)
        t = now
    alloc = engine.kv.allocator
    for n in plan.scrub_counts:
        if n <= alloc.n_free:
            engine.kv.deref(alloc.alloc(n))
    if engine.prefix_cache is not None:
        engine.prefix_cache.clear()
    return steps


@dataclasses.dataclass
class ReqRecord:
    req: Req
    due: float                   # scheduled arrival, perf_counter seconds
    submit: float | None = None
    token_times: list[float] = dataclasses.field(default_factory=list)
    handle: object = None        # the engine's EngineRequest
    hw_prompt: int = 0           # high-water mark of prompt rows cached


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    decode_ctx: list[int]        # context length of each decode row
    # (start, end, completed) rows cached per prefilling request
    prefill: list[tuple[int, int, bool]]
    prompt_rows: int             # new prompt rows (high-water growth)


@dataclasses.dataclass
class Record:
    kind: str
    reqs: list[ReqRecord]
    steps: list[StepRecord]
    window: tuple[float, float]          # measured window (perf_counter)
    traced: tuple[float, float] | None   # profiler window, if any
    window_traces: int = 0               # jit traces inside the window
    engine: object = None
    dims: object = None                  # the model module's Dims
    trace: dict | None = None            # reduced profiler trace
    peaks: dict | None = None

    def steps_in(self, lo: float, hi: float) -> list[StepRecord]:
        return [s for s in self.steps if s.t0 >= lo and s.t1 <= hi]


class _Tracer:
    """jax.profiler over a sub-window, with host annotations."""

    def __init__(self, out_dir):
        import jax
        self.jax = jax
        self.dir = out_dir
        self.on = False

    def start(self):
        self.jax.profiler.start_trace(str(self.dir))
        self.on = True

    def stop(self) -> float:
        """Stop; returns the traced window's end, read before the trace is
        written out."""
        t = time.perf_counter()
        if self.on:
            self.jax.profiler.stop_trace()
            self.on = False
        return t


def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _step(engine, records_by_rid, rec_steps):
    """One engine step with the client-side bookkeeping around it."""
    before = {}
    decoding = set()
    for r in engine.queue + engine.active:
        before[r.rid] = r.n_cached
    for r in engine.decoding:
        decoding.add(r.rid)
    t0 = time.perf_counter()
    with _annotate("bench.step"):
        finished = engine.step()
    t1 = time.perf_counter()
    dec, pre, prompt_rows = [], [], 0
    still = {r.rid for r in engine.prefilling}
    for rid, n0 in before.items():
        rr = records_by_rid.get(rid)
        if rr is None:
            continue
        n1 = rr.handle.n_cached
        if n1 <= n0:
            continue
        if rid in decoding:
            dec.append(n1)
        else:
            pre.append((n0, n1, rid not in still))
        hw = min(n1, len(rr.req.prompt))
        if hw > rr.hw_prompt:
            prompt_rows += hw - rr.hw_prompt
            rr.hw_prompt = hw
    rec_steps.append(StepRecord(t0, t1, dec, pre, prompt_rows))
    return finished


def _submit(engine, rr: ReqRecord, records_by_rid):
    EngineRequest, SamplingParams, _ = _program()
    q = rr.req
    h = EngineRequest(rid=q.rid, prompt=q.prompt, sampling=SamplingParams(
        temperature=q.temperature, max_new=q.max_new, top_p=q.top_p))
    rr.handle = h
    records_by_rid[q.rid] = rr

    def on_token(_rid, _tok, rr=rr):
        rr.token_times.append(time.perf_counter())

    with _annotate("bench.submit"):
        engine.submit(h, on_token=on_token)
    rr.submit = time.perf_counter()


def _count_traces():
    """A counter of jit traces (new shapes) from jax.monitoring."""
    import jax
    box = {"n": 0, "on": False}

    def listener(event, _dur, **_kw):
        if box["on"] and event.endswith("jaxpr_trace_duration"):
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return box


def drive(engine, reqs: list[Req], mix: dict, seconds: float,
          trace_dir=None, trace_s: float = 0.0) -> Record:
    """Offer the traffic and record it. Open loop: arrivals on schedule,
    the window opens `ramp_s` after the first arrival and closes
    `seconds` later; arrivals go on until every request due in the window
    has its first token or `grace_s` has passed. Backlog: every request
    queued at once; the window opens when every slot of the batch first
    decodes, and closes at the first step boundary `seconds` later."""
    box = _count_traces()
    tracer = _Tracer(trace_dir) if trace_dir is not None else None
    by_rid: dict[int, ReqRecord] = {}
    steps: list[StepRecord] = []
    t_start = time.perf_counter()
    recs = [ReqRecord(q, t_start + q.arrival_s) for q in reqs]
    traced = None
    if mix["kind"] == "open_loop":
        w0 = t_start + mix["ramp_s"]
        w1 = w0 + seconds
        due = [r for r in recs if w0 <= r.due < w1]
        nxt = 0
        while True:
            now = time.perf_counter()
            while nxt < len(recs) and recs[nxt].due <= now:
                _submit(engine, recs[nxt], by_rid)
                nxt += 1
            if tracer is not None:
                if not tracer.on and traced is None and now >= w0:
                    tracer.start()
                    traced = (time.perf_counter(), None)
                elif tracer.on and now >= min(w0 + trace_s, w1):
                    traced = (traced[0], tracer.stop())
            box["on"] = w0 <= now < w1
            if now >= w1 and (now >= w1 + mix["grace_s"] or all(
                    r.token_times or (r.handle is not None and r.handle.done)
                    for r in due)):
                break
            if engine.queue or engine.active:
                _step(engine, by_rid, steps)
            elif nxt < len(recs):
                with _annotate("bench.idle"):
                    time.sleep(max(0.0, min(recs[nxt].due - now, 0.002)))
            else:
                break
        window = (w0, w1)
    else:
        for r in recs:
            _submit(engine, r, by_rid)
        # the window opens once the batch is full (every slot decoding, or
        # nothing left to prefill), so the window sees steady refills
        while len(engine.decoding) < engine.max_seqs and (
                engine.queue or engine.prefilling):
            _step(engine, by_rid, steps)
        w0 = time.perf_counter()
        box["on"] = True
        if tracer is not None:
            tracer.start()
            traced = (time.perf_counter(), None)
        while engine.queue or engine.active:
            now = time.perf_counter()
            if tracer is not None and tracer.on and now >= w0 + trace_s:
                traced = (traced[0], tracer.stop())
            if now >= w0 + seconds:
                break
            _step(engine, by_rid, steps)
        w1 = time.perf_counter()
        box["on"] = False
        window = (w0, w1)
    if tracer is not None and tracer.on:
        traced = (traced[0], tracer.stop())
    box["on"] = False
    return Record(kind=mix["kind"], reqs=recs, steps=steps, window=window,
                  traced=traced, window_traces=box["n"], engine=engine)
