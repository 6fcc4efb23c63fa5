"""Continuous-batching scheduler over the paged serving state.

Scheduling model (one `step()` = one engine iteration):

  1. **Lifecycle sweep** — injected faults (cancel/expiry chaos from an
     attached `FaultPlan`) and per-request deadlines are applied at the
     step boundary: a cancelled or expired request leaves whatever phase
     it is in with its pages and register slot scrubbed and returned.
  2. **Admission** — two policies, selected at construction:

     * `"optimistic"` (default): admit when the pages for the request's
       *prompt* plus a small headroom watermark fit next to the pages
       already committed. Utilization under bursty traffic is bounded by
       real demand, not by worst-case reservations — the trade is that
       the pool can genuinely exhaust mid-decode, which preemption
       (below) recovers from.
     * `"reserve"`: the safety baseline — admit only when
       `pages_for(prompt + max_new)` worst-case pages fit, so a running
       sequence can never hit an out-of-pages fault. Utilization caps
       exactly when traffic is heaviest (the pool fills with pages
       nobody has written yet).

     Committed pages are tracked as a running total (`_committed_total`,
     updated at admit/growth/finish/preempt/cancel), so admission is
     O(queue), not O(queue · active). Backoff-waiting replays are
     skipped; otherwise admission blocks head-of-line for fairness.

     With the **prefix cache** enabled (`prefix_cache=True`, kv-only
     specs), admission additionally matches the request's token stream
     against the radix tree (`radix.RadixCache`): fully-matched pages
     are incref'd straight into the block table, a partial-page match is
     recovered by copying that page (COW) into a private one, and
     `n_cached` starts at the hit length so chunked prefill begins at
     the divergence offset. Finished requests donate their page-aligned
     prefix back to the tree at release (under an LRU page budget)
     instead of scrubbing it; under page pressure the scheduler evicts
     cached prefixes (`_reclaim`) before preempting any live sequence.
  3. **Decode** — every generating sequence advances one token in a
     single batched `forward_chunk` call with per-slot fill positions,
     block-table rows, and register slot indices, padded to `max_seqs`
     rows so the jit cache shape is fixed. Before the dispatch, page
     growth runs under the preemption guard (below).
  4. **Chunked prefill** — the rest of the per-step token budget goes to
     the head-of-line prompt, `prefill_chunk` tokens at a time, chunks
     padded to the next power of two. A *replay* (preempted request)
     prefills `prompt + generated` through exactly the same path.

**Preemption / replay contract.** When page growth would exhaust the
allocator (really, or via an injected fault), the scheduler preempts a
victim — the active page-holding request with the fewest generated
tokens, latest-admitted breaking ties — releasing its pages and slot
through the same scrub path `release()` uses, and re-queues it at the
front with exponential step backoff. Replay recomputes the victim by
prefilling `prompt + generated` (all host-known — no swap traffic) and
must reproduce the *identical* continuation: greedy decoding is
deterministic, and sampling keys are derived per `(rid, position)` from
the engine seed (`_row_keys`), never from a global step key, so a
replayed sampled continuation is bit-identical no matter how the
interleaving changed. A request preempted more than `max_preemptions`
times fails terminally (`failed="preempted..."`) instead of livelocking.

**Swap-vs-replay cost rule.** With a host swap tier attached
(`swap_host_mb`, kv-only specs), `_handle_exhaustion` chooses per
victim: swap-out parks the victim's exclusively-held pages in host
memory (round-trip bytes = `2 · pages · page_bytes`) while replay
re-prefills `len(prompt + generated)` tokens — under the default
`"cost"` policy the victim swaps when the bytes are no more than
`swap_break_even_bytes_per_token` per replayed token (quantized int4
KV pages shrink the byte side 4-8x, tipping long sequences toward
swap), bounded by the host budget; `"always"`/`"never"` force either
arm. A swapped victim waits in the queue like a replay (front
insertion, exponential backoff, headroom waived) but re-admits by
swapping its pages back in — block-table row patched in place, zero
recomputed tokens, bit-identical continuation. Swap transfers can fail
(injected `SwapFault` or a genuinely full allocator at swap-in): the
engine retries with exponential backoff up to `swap_max_retries`, then
degrades the request to recompute-by-replay (counted
`engine.swap.fallbacks`, and from there the normal preemption bound
applies). Swap-outs do NOT count against `max_preemptions` — the
livelock bound protects against repeated *recompute* work, and a swap
round-trip loses none.

**Graceful degradation rails.** `drain()` stops admission (never-
admitted queued requests terminate `cancelled`), finishes all in-flight
work — including parked replays and swapped-out residents — then
asserts balanced books and zero non-scratch residency on every tier.
A non-finite max-logit in any fused sampling dispatch (a poisoned
adapter output: NaN/Inf) terminates only the poisoned rows with
`outcome="failed"` (counter `engine.requests.poisoned`) instead of
sampling garbage into the stream; and a raising `on_token` callback is
caught per-callback (`engine.stream.callback_errors`), dropped, and
never blocks delivery to other streams.

**Stall detection.** If nothing is active and an admission-eligible
request still cannot be admitted, no future step can make progress; the
scheduler raises `EngineStalledError` naming who is blocked and on how
many pages instead of spinning forever. Optimistic `submit()` rejects
up front prompts whose pages can never fit beside the headroom.

Telemetry: every engine counter lives in a `serve.telemetry`
`MetricsRegistry` (`self.metrics`), including the robustness families —
`engine.preemptions`, `engine.requests.cancelled/expired/failed`,
`engine.replayed_prefill_tokens`, `engine.dispatch.faults`, and the
live/peak page-utilization gauges, refreshed when a snapshot is taken.

Step phases: `step()` runs as `engine.step` → `engine.admit`, then per
dispatch `engine.prepare.<phase>` (page growth, block table, argument
upload), `engine.dispatch.<phase>` (the jitted call until it returns:
enqueue, plus trace and compile on a new shape), `engine.wait.<phase>`
(the host blocks on the sampled tokens) and `engine.commit.<phase>`, then
`engine.account` and `engine.stream`. Each phase is a
`jax.profiler.TraceAnnotation`, so under the profiler the spans land on
the device trace's clock; with a `Tracer` attached the same spans go to
its engine track. Each fused program is named `engine_<phase>[_<variant>]`
on the device. `engine.dispatch.new_shapes` counts dispatches whose
program signature this engine had not dispatched before, and
`engine.step.host_s` is each step's wall minus its waits: the time a
synchronous engine leaves the chip idle. The `Tracer` also records
request lifecycles, and optional `QualityProbes` sample rotation-quality
stats; all of it stays bit-path-neutral.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.serve.telemetry.metrics import MetricsRegistry
from repro.serve.telemetry.quality import QualityProbes
from repro.serve.telemetry.trace import PID_REQUESTS, Tracer

from .adapter import ServableModel
from .faults import DispatchFault, FaultPlan, SwapFault
from .pages import PagedKVCache, pages_for
from .radix import RadixCache


class EngineStalledError(RuntimeError):
    """Admission can never proceed: nothing is active and an eligible
    queued request still does not fit. Raised with a per-request
    diagnosis instead of letting `run()` spin forever."""


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _row_keys(base, rids, positions):
    """Replay-stable sampling keys: one PRNG key per batch row, derived
    from the `(rid, position)` pair — never from a global step key — so
    the token sampled at a given position of a given request is the same
    no matter which step, batch slot, or replay attempt produces it."""
    def one(r, p):
        return jax.random.fold_in(jax.random.fold_in(base, r), p)

    return jax.vmap(one)(rids, positions)


@functools.partial(jax.jit, static_argnames=("filtered",))
def _sample_tokens(keys, logits, temps, top_ks, top_ps, *, filtered=True):
    """One fused device call: greedy rows where temp == 0; elsewhere
    categorical over logits/temp restricted to the top-k tokens (k == 0
    disables) and then the nucleus — the smallest set whose probability
    mass reaches top_p (top_p >= 1 disables). Each row samples with its
    own `(rid, position)`-derived key (`keys` [B]), so stochastic rows
    are replay-stable. `filtered=False` (static — the scheduler knows
    host-side when every row has filtering off) skips the two full-vocab
    sorts so pure-greedy/temperature batches keep their pre-top-k/p
    cost."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    if filtered:
        desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(desc,
                                  jnp.clip(top_ks - 1, 0, v - 1)[:, None],
                                  axis=-1)
        keep = (top_ks <= 0)[:, None] | (scaled >= kth)
        scaled = jnp.where(keep, scaled, -jnp.inf)
        probs = jax.nn.softmax(scaled, axis=-1)
        sp = jnp.sort(probs, axis=-1)[:, ::-1]
        cum = jnp.cumsum(sp, axis=-1)
        # a sorted token enters the nucleus while the mass before it is < p
        keep_sorted = ((cum - sp) < top_ps[:, None]) \
            | (top_ps >= 1.0)[:, None]
        thresh = jnp.min(jnp.where(keep_sorted, sp, jnp.inf), axis=-1)
        scaled = jnp.where(probs >= thresh[:, None], scaled, -jnp.inf)
    sampled = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg))(keys, scaled)
    return jnp.where(temps > 0, sampled, greedy)


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling: temperature 0 → greedy argmax. `top_k` > 0
    restricts sampling to the k most likely tokens, `top_p` < 1 to the
    nucleus; `stop` is a tuple of token-id sequences that end generation
    early (the matched suffix is kept in `generated`)."""
    temperature: float = 0.0
    max_new: int = 8
    top_k: int = 0
    top_p: float = 1.0
    stop: tuple = ()


@dataclasses.dataclass
class EngineRequest:
    rid: int
    prompt: list[int]
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    deadline_s: float | None = None  # TTL from submit, step-boundary checked
    generated: list[int] = dataclasses.field(default_factory=list)
    # per generated token: float32 logits row (only when record_logits)
    step_logits: list[np.ndarray] = dataclasses.field(default_factory=list)
    stop_hit: bool = False     # a stop sequence ended generation early
    # --- terminal lifecycle outcomes (at most one is ever set) ---
    cancelled: bool = False    # cancel(rid) took it out
    expired: bool = False      # deadline / injected TTL fired
    failed: str | None = None  # terminal failure, e.g. preemption limit
    # --- engine-internal state ---
    n_cached: int = 0          # KV rows already written for this sequence
    n_streamed: int = 0        # generated tokens already sent to on_token
    next_token: int | None = None
    n_preempted: int = 0       # times this request lost its pages
    admit_seq: int = -1        # monotonic admission order (victim pick)
    not_before_step: int = 0   # replay backoff: earliest re-admission step
    swapped: bool = False      # pages parked in the host tier (queued)
    n_swapped: int = 0         # times this request swapped out
    swap_retries: int = 0      # failed swap-in attempts since swap-out
    t_submit: float | None = None   # perf_counter at submit (telemetry)
    t_admit: float | None = None    # perf_counter at admission

    @property
    def done(self) -> bool:
        return (self.stop_hit or self.cancelled or self.expired
                or self.failed is not None
                or len(self.generated) >= self.sampling.max_new)

    @property
    def outcome(self) -> str | None:
        """Why the request ended: "length" | "stop" | "cancelled" |
        "expired" | "failed", or None while still in flight."""
        if self.cancelled:
            return "cancelled"
        if self.expired:
            return "expired"
        if self.failed is not None:
            return "failed"
        if self.stop_hit:
            return "stop"
        if len(self.generated) >= self.sampling.max_new:
            return "length"
        return None


class ServeEngine:
    """Paged-KV continuous-batching engine over any `ServableModel`."""

    def __init__(self, adapter: ServableModel, *, n_pages: int,
                 page_size: int = 16, max_seqs: int = 4,
                 prefill_chunk: int = 8, token_budget: int | None = None,
                 seed: int = 0, record_logits: bool = False,
                 admission: str = "optimistic",
                 headroom_pages: int | None = None,
                 max_preemptions: int = 3,
                 max_context: int | None = None,
                 deadline_s: float | None = None,
                 prefix_cache: bool = False,
                 prefix_cache_pages: int | None = None,
                 swap_host_mb: float | None = None,
                 swap_policy: str = "cost",
                 swap_max_retries: int = 3,
                 swap_break_even_bytes_per_token: float = 4096.0,
                 faults: FaultPlan | None = None,
                 tracer: Tracer | None = None,
                 quality_probes: QualityProbes | None = None):
        if admission not in ("optimistic", "reserve"):
            raise ValueError(f"admission must be 'optimistic' or 'reserve', "
                             f"got {admission!r}")
        if swap_policy not in ("never", "cost", "always"):
            raise ValueError(f"swap_policy must be 'never', 'cost', or "
                             f"'always', got {swap_policy!r}")
        self.adapter = adapter
        self.spec = adapter.state_spec
        self.max_seqs = max_seqs
        self.prefill_chunk = prefill_chunk
        self.token_budget = token_budget or max(max_seqs, prefill_chunk)
        self.record_logits = record_logits
        self.admission = admission
        self.max_preemptions = max_preemptions
        self.default_deadline_s = deadline_s
        self.faults = faults
        # one register slot per concurrent sequence (+ the scratch slot):
        # admission is bounded by max_seqs, so slots can never run out
        # before sequence slots do
        n_slots = max_seqs + 1
        self.kv = PagedKVCache(adapter.init_state(n_pages, page_size,
                                                  n_slots),
                               n_pages, page_size, n_slots=n_slots)
        cap = self.kv.allocator.capacity
        # headroom watermark: pages optimistic admission keeps free for
        # decode growth of the already-running batch (waived for replay
        # re-admission — a replay's requirement is already its real
        # footprint, and waiving it keeps replays always admittable)
        self.headroom_pages = min(max_seqs, cap // 4) \
            if headroom_pages is None else headroom_pages
        # context window: explicit, else the pool bound for kv specs
        # (register-only state never grows, so there is no implied bound)
        self.max_context = max_context if max_context is not None \
            else (cap * page_size if self.spec.kv else None)
        # prefix-sharing radix cache: kv-only specs (register/SSM state is
        # position-dependent — see StateSpec.prefix_shareable)
        if prefix_cache and not self.spec.prefix_shareable:
            raise ValueError(
                f"adapter {adapter.name!r} carries register state: SSM "
                "state is position-dependent, so the prefix cache cannot "
                "serve this spec")
        self.prefix_cache = RadixCache(self.kv, prefix_cache_pages) \
            if prefix_cache else None
        # host swap tier: a byte budget for parking preemption victims'
        # KV pages instead of recomputing them (kv-only specs — register
        # state is fixed-size slot-resident and never paged out)
        self.swap_policy = swap_policy if swap_host_mb else "never"
        self.swap_max_retries = swap_max_retries
        self.swap_break_even_bytes_per_token = swap_break_even_bytes_per_token
        if swap_host_mb and swap_policy != "never":
            if self.spec.register:
                raise ValueError(
                    f"adapter {adapter.name!r} carries register state: "
                    "fixed-size SSM slots are not paged, so the host swap "
                    "tier cannot serve this spec (recompute-by-replay "
                    "still covers it)")
            self.kv.attach_host_pool(swap_host_mb)
        self._draining = False
        self.queue: list[EngineRequest] = []
        self._callbacks: dict[int, Any] = {}   # rid → on_token streaming cb
        self.prefilling: list[EngineRequest] = []
        self.decoding: list[EngineRequest] = []
        self._committed: dict[int, int] = {}   # rid → committed page count
        self._committed_total = 0              # == sum(_committed.values())
        self._terminal: list[EngineRequest] = []   # drained by step()
        self._step_index = 0                   # never reset (faults key on it)
        self._admit_seq = 0
        self._base_key = jax.random.PRNGKey(seed)
        # jit cache for the fused phase dispatches, keyed on the kernels
        # flag (mirrors QuantizedDenseLM._jitted)
        self._jit_cache: dict = {}
        # (program, shape) signatures dispatched so far: a new one traces
        self._dispatched: set = set()
        self._waited = 0.0                     # this step's wait phases, s
        # telemetry: the registry owns every counter the old plain-int
        # attributes held (read-only property views keep the old names
        # alive); tracer and quality probes are opt-in and bit-path-
        # neutral. Page-walk accounting semantics are unchanged:
        # `engine.pages_walked` counts what the ragged early-exit
        # actually walks (ceil(len/page_size) live columns per sequence),
        # `engine.pages_walked_dense` what the pre-flash-decode kernel
        # walked (every padded batch row × every table column).
        self.metrics = MetricsRegistry()
        self.tracer = tracer
        self.quality_probes = quality_probes
        if quality_probes is not None:
            if not getattr(adapter, "supports_quality_probes", False):
                raise ValueError(
                    f"adapter {adapter.name!r} does not support quality "
                    "probes (integer path only)")
            quality_probes.bind(self.metrics)
        self._register_metrics()

    # ------------------------------------------------------------------
    # bookkeeping
    # ------------------------------------------------------------------

    @property
    def active(self) -> list[EngineRequest]:
        return self.prefilling + self.decoding

    def submit(self, req: EngineRequest,
               on_token: Callable[[int, int], None] | None = None):
        """Queue a request. `on_token(rid, token)`, when given, streams
        every generated token at the step boundary that produced it —
        after the step's device work and bookkeeping, so the callback can
        never perturb engine state mid-phase. Replays never re-deliver: a
        preempted request resumes streaming where it left off (its
        recomputed tokens are bit-identical, so nothing is retracted)."""
        if self._draining:
            raise RuntimeError(
                "engine is draining: new requests are not accepted")
        if not req.prompt:
            raise ValueError("empty prompt")
        if req.sampling.max_new < 1:
            raise ValueError("max_new must be >= 1")
        if req.sampling.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 disables)")
        if not 0.0 < req.sampling.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if any(len(seq) == 0 for seq in req.sampling.stop):
            raise ValueError("stop sequences must be non-empty")
        if req.n_cached or req.generated or req.done:
            raise ValueError(f"request {req.rid} carries stale engine "
                             "state; submit a fresh EngineRequest")
        if any(req.rid == r.rid for r in self.queue + self.active):
            raise ValueError(f"rid {req.rid} already queued or active")
        total = len(req.prompt) + req.sampling.max_new
        if self.max_context is not None and total > self.max_context:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)} tokens) + "
                f"max_new ({req.sampling.max_new}) exceeds the model "
                f"context window ({self.max_context} tokens)")
        if self.spec.kv:
            worst = pages_for(total, self.kv.page_size)
            cap = self.kv.allocator.capacity
            if worst > cap:
                raise ValueError(
                    f"request {req.rid} needs {worst} pages; pool capacity "
                    f"is {cap}")
            if self.admission == "optimistic" \
                    and pages_for(len(req.prompt), self.kv.page_size) \
                    + self.headroom_pages > cap:
                raise ValueError(
                    f"request {req.rid}: prompt pages + headroom "
                    f"({self.headroom_pages}) exceed pool capacity {cap} — "
                    "it could never be admitted (shrink the prompt or the "
                    "headroom watermark)")
        if req.deadline_s is None:
            req.deadline_s = self.default_deadline_s
        req.t_submit = time.perf_counter()
        self.queue.append(req)
        if on_token is not None:
            self._callbacks[req.rid] = on_token
        self.metrics.counter("engine.requests.submitted").inc()
        if self.tracer:
            self.tracer.begin("request", pid=PID_REQUESTS, tid=req.rid,
                              args={"prompt_tokens": len(req.prompt),
                                    "max_new": req.sampling.max_new})
            self.tracer.begin("queued", pid=PID_REQUESTS, tid=req.rid)

    def _stream(self, req: EngineRequest) -> list[int]:
        """The token stream prefill must cache: the prompt, plus — for a
        preempted request being replayed — every already-generated token
        (all host-known, so recovery needs no swap traffic)."""
        return req.prompt + req.generated

    def _pages_needed(self, req: EngineRequest) -> int:
        """KV pages admission requires for this request (0 for
        register-only models — their state never grows): the worst case
        under `"reserve"`, the prefill stream's pages under
        `"optimistic"` (growth is backed by preemption)."""
        if not self.spec.kv:
            return 0
        if req.swapped:
            # a swapped-out request re-admits by allocating device pages
            # for exactly its host-resident entries — its retained shared
            # pages never left the device and are already committed
            return sum(1 for e in self.kv.tables[req.rid]
                       if not isinstance(e, int))
        if self.admission == "reserve":
            return pages_for(len(req.prompt) + req.sampling.max_new,
                             self.kv.page_size)
        return pages_for(len(self._stream(req)), self.kv.page_size)

    def _admit(self):
        cap = self.kv.allocator.capacity
        i = 0
        while i < len(self.queue) and len(self.active) < self.max_seqs:
            req = self.queue[i]
            if req.not_before_step > self._step_index:
                i += 1               # replay backoff: try later entries
                continue
            need = self._pages_needed(req)
            headroom = self.headroom_pages \
                if self.admission == "optimistic" \
                and not (req.n_preempted or req.swapped) else 0
            if self._committed_total + need + headroom > cap:
                self.metrics.counter("engine.admission.blocked").inc()
                return           # head-of-line blocks until pages free up
            if req.swapped:
                if not self._try_swap_in(req):
                    # retry scheduled (backoff), degraded to replay, or
                    # terminally failed — either way queue[i] now either
                    # skips on not_before_step or is a different request
                    continue
                self.queue.remove(req)
                req.admit_seq = self._admit_seq
                self._admit_seq += 1
                # a mid-prefill victim resumes prefill at its preserved
                # n_cached; a decode victim rejoins the batched decode
                # (its sampled-but-uncached next_token rides along)
                phase = "decode" if req.next_token is not None \
                    else "prefill"
                (self.decoding if phase == "decode"
                 else self.prefilling).append(req)
                self.metrics.counter("engine.requests.admitted").inc()
                if self.tracer:
                    self.tracer.end("queued", pid=PID_REQUESTS, tid=req.rid)
                    self.tracer.instant("swapped_in", pid=PID_REQUESTS,
                                        tid=req.rid)
                    self.tracer.begin(phase, pid=PID_REQUESTS, tid=req.rid)
                continue
            self.queue.pop(i)
            self.kv.open(req.rid)     # before committing: if this raises,
            self._committed[req.rid] = need   # no reservation leaks
            self._committed_total += need
            if self.prefix_cache is not None:
                self._attach_prefix(req)
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            self.prefilling.append(req)
            self.metrics.counter("engine.requests.admitted").inc()
            if req.t_admit is None:
                # client-visible queueing delay: time to *first* admission
                # (a replay's re-admission shows up in the preemption
                # counters and e2e latency, not here)
                req.t_admit = time.perf_counter()
                self.metrics.histogram("engine.admission.wait_s").observe(
                    max(req.t_admit - req.t_submit, 0.0))
            if self.tracer:
                self.tracer.end("queued", pid=PID_REQUESTS, tid=req.rid)
                self.tracer.begin("prefill", pid=PID_REQUESTS, tid=req.rid)
                if self.spec.register:
                    self.tracer.instant(
                        "alloc_slot", pid=PID_REQUESTS, tid=req.rid,
                        args={"slot": self.kv.slots[req.rid]})

    def _alloc_pages(self, n: int) -> list[int]:
        """Allocate `n` pages, evicting cached prefixes under pressure —
        the tree gives pages back before any live sequence is preempted."""
        try:
            return self.kv.allocator.alloc(n)
        except MemoryError:
            if self.prefix_cache is None or not self.prefix_cache.evict(n):
                raise
            return self.kv.allocator.alloc(n)

    def _attach_prefix(self, req: EngineRequest):
        """Seed a just-admitted request's block table from the radix
        tree: incref the longest fully-matched page run, and when the
        match extends into a page partially (or the last-token clamp cuts
        one short), copy that page (COW) so the request can write into
        its private copy. `n_cached` starts at the hit length, so chunked
        prefill begins at the divergence offset."""
        stream = self._stream(req)
        pages, cow = self.prefix_cache.match(stream)
        ps = self.kv.page_size
        # the final stream position must always be recomputed: its logits
        # seed the next sampled token, and prefill is the only phase that
        # produces them
        hit = min(len(pages) * ps + (cow[1] if cow else 0), len(stream) - 1)
        m = self.metrics
        if hit <= 0:
            m.counter("engine.prefix.misses").inc()
            return
        n_full, extra = divmod(hit, ps)
        shared = pages[:n_full]
        src = (pages[n_full] if n_full < len(pages) else cow[0]) \
            if extra else None
        alloc = self.kv.allocator
        alloc.incref(shared)      # our references; also pins them against
        dst = None                # the eviction _alloc_pages may trigger
        if src is not None:
            alloc.incref([src])   # pin the COW source too
            try:
                dst = self._alloc_pages(1)[0]
            except MemoryError:
                # no room for a private copy — fall back to the full-page
                # hit (deref can never scrub: the tree still holds src)
                self.kv.deref([src])
                hit, src = n_full * ps, None
                if hit == 0:
                    m.counter("engine.prefix.misses").inc()
                    return
        table = self.kv.tables[req.rid]
        table.extend(shared)
        if src is not None:
            self.kv.cow_copy(src, dst)
            self.kv.deref([src])          # unpin; our copy carries on
            table.append(dst)
            m.counter("engine.prefix.cow_copies").inc()
        req.n_cached = hit
        m.counter("engine.prefix.hits").inc()
        m.counter("engine.prefix.hit_tokens").inc(hit)
        if self.tracer:
            self.tracer.instant("prefix_hit", pid=PID_REQUESTS, tid=req.rid,
                                args={"tokens": hit, "cow": src is not None})

    def _release(self, req: EngineRequest, adopted: int = 0):
        """Return an admitted request's pages/slot and its commitment.
        The first `adopted` table entries' references were consumed by
        the radix tree (see `_finish`) and are skipped."""
        self.kv.release(req.rid, adopted=adopted)
        self._committed_total -= self._committed.pop(req.rid)

    def _finish(self, req: EngineRequest):
        adopted = 0
        if self.prefix_cache is not None:
            # donate the finished stream's full pages to the tree: insert
            # consumes our reference on every page passed (adopting new
            # branches, dereffing duplicates of already-cached ones), so
            # release skips exactly that many table entries
            full = req.n_cached // self.kv.page_size
            if full:
                stream = self._stream(req)
                table = self.kv.tables[req.rid]
                self.prefix_cache.insert(stream[:full * self.kv.page_size],
                                         table[:full])
                adopted = full
        self._release(req, adopted=adopted)
        m = self.metrics
        m.counter("engine.requests.finished").inc()
        if req.stop_hit:
            m.counter("engine.requests.stop_hits").inc()
        if req.t_submit is not None:
            m.histogram("engine.request.e2e_s").observe(
                max(time.perf_counter() - req.t_submit, 0.0))
        if self.tracer:
            self.tracer.end("decode", pid=PID_REQUESTS, tid=req.rid)
            self.tracer.end("request", pid=PID_REQUESTS, tid=req.rid,
                            args={"generated": len(req.generated),
                                  "stop_hit": req.stop_hit})

    # ------------------------------------------------------------------
    # lifecycle: cancel / expire / preempt
    # ------------------------------------------------------------------

    def _phase_of(self, req: EngineRequest) -> str:
        if req in self.queue:
            return "queued"
        if req in self.prefilling:
            return "prefill"
        if req in self.decoding:
            return "decode"
        raise ValueError(f"rid {req.rid} is not queued or active")

    def _by_rid(self, rid: int) -> EngineRequest:
        for r in self.queue + self.active:
            if r.rid == rid:
                return r
        raise ValueError(f"rid {rid} is not queued or active")

    def _terminate(self, req: EngineRequest, outcome: str):
        """Take `req` out of whatever phase it is in: pages and slot
        scrubbed + released (admitted requests), books rebalanced, the
        terminal flag set, and the request queued for return from the
        current/next `step()`."""
        phase = self._phase_of(req)
        if phase == "queued":
            self.queue.remove(req)
            if req.swapped:
                # a swapped-out request parked in the queue still holds
                # host slots and (possibly) retained shared device pages
                self._release(req)
                req.swapped = False
        else:
            (self.prefilling if phase == "prefill"
             else self.decoding).remove(req)
            self._release(req)
        if outcome == "cancelled":
            req.cancelled = True
        elif outcome == "expired":
            req.expired = True
        # "failed" requests carry their reason in req.failed already
        self.metrics.counter(f"engine.requests.{outcome}").inc()
        if self.tracer:
            self.tracer.end(phase, pid=PID_REQUESTS, tid=req.rid)
            self.tracer.end("request", pid=PID_REQUESTS, tid=req.rid,
                            args={"outcome": outcome,
                                  "generated": len(req.generated)})
        self._terminal.append(req)

    def cancel(self, rid: int) -> EngineRequest:
        """Cancel a queued or mid-flight request: its pages and register
        slot are scrubbed and returned, books stay balanced, and the
        request (marked `cancelled`) is also returned from the next
        `step()`/`run()`."""
        req = self._by_rid(rid)
        self._terminate(req, "cancelled")
        return req

    def _expire_deadlines(self):
        now = time.perf_counter()
        for req in self.queue + self.active:
            if req.deadline_s is not None and req.t_submit is not None \
                    and now - req.t_submit > req.deadline_s:
                self._terminate(req, "expired")

    def _apply_faults(self):
        if self.faults is None:
            return
        live = sorted(r.rid for r in self.queue + self.active)
        for rid in self.faults.cancels_due(self._step_index, live):
            self.cancel(rid)
        live = sorted(r.rid for r in self.queue + self.active)
        for rid in self.faults.expiries_due(self._step_index, live):
            self._terminate(self._by_rid(rid), "expired")

    def _maybe_dispatch_fault(self, phase: str):
        if self.faults is None:
            return
        kind = self.faults.take_dispatch_fault(self._step_index)
        if kind == "delay":
            self.metrics.counter("engine.dispatch.faults").inc()
            time.sleep(self.faults.dispatch_delay_s)
        elif kind == "fail":
            raise DispatchFault(
                f"injected dispatch failure at step {self._step_index} "
                f"({phase})")

    def _preempt(self, req: EngineRequest):
        """Victimize an active request: scrub + release its pages (and
        slot) through the normal release path, then either re-queue it
        at the front as a replay (prefill of prompt + generated, with
        exponential step backoff) or — past `max_preemptions` — fail it
        terminally instead of livelocking."""
        phase = self._phase_of(req)
        (self.prefilling if phase == "prefill"
         else self.decoding).remove(req)
        m = self.metrics
        m.counter("engine.preemptions").inc()
        # replayed_prefill_tokens is charged by the replay's prefill for
        # what it *actually* recomputes — not here for what was lost: a
        # victim whose prefix is still resident in the radix tree gets
        # most of these rows back as pointer updates, and the shared
        # pages released below are unpinned, never scrubbed
        self._release(req)
        req.n_preempted += 1
        req.n_cached = 0
        req.next_token = None
        if self.tracer:
            self.tracer.end(phase, pid=PID_REQUESTS, tid=req.rid)
            self.tracer.instant("preempted", pid=PID_REQUESTS, tid=req.rid,
                                args={"n_preempted": req.n_preempted})
        if req.n_preempted > self.max_preemptions:
            req.failed = (f"preempted {req.n_preempted} times "
                          f"(max_preemptions={self.max_preemptions})")
            self.metrics.counter("engine.requests.failed").inc()
            if self.tracer:
                self.tracer.end("request", pid=PID_REQUESTS, tid=req.rid,
                                args={"outcome": "failed",
                                      "generated": len(req.generated)})
            self._terminal.append(req)
        else:
            req.not_before_step = \
                self._step_index + 2 ** (req.n_preempted - 1)
            if self.tracer:
                self.tracer.begin("queued", pid=PID_REQUESTS, tid=req.rid)
            self.queue.insert(0, req)

    def _should_swap(self, victim: EngineRequest) -> bool:
        """The swap-vs-replay cost rule: park the victim's exclusive
        pages in the host tier when (a) a tier exists with room for
        them, (b) there is anything exclusive to move at all (a victim
        whose pages are all radix-shared frees nothing by swapping), and
        (c) the policy's byte-vs-token arithmetic favors it: round-trip
        bytes (out now, in at re-admission) vs the tokens a replay would
        re-prefill, scaled by the configured break-even traffic per
        recomputed token. Quantized int4/int8 KV pages shrink the byte
        side 4-8x — exactly what tips long sequences toward swap."""
        host = self.kv.host_pool
        if host is None or self.swap_policy == "never":
            return False
        pages = self.kv.swap_eligible_pages(victim.rid)
        if not pages or len(pages) > host.n_free:
            return False
        if self.swap_policy == "always":
            return True
        move_bytes = 2 * len(pages) * self.kv.page_bytes
        replay_tokens = len(self._stream(victim))
        return move_bytes \
            <= replay_tokens * self.swap_break_even_bytes_per_token

    def _swap_out(self, req: EngineRequest):
        """Swap the victim's exclusive pages to the host tier instead of
        scrubbing them: the device copies free for the starving grower,
        and the victim re-queues at the front — like a replay, but its
        re-admission is a swap-in (zero recomputed tokens) rather than a
        re-prefill. Raises `SwapFault` (injected) before any mutation,
        letting `_handle_exhaustion` fall back to the replay arm.
        Swap-outs do not count against `max_preemptions`: that bound
        protects against repeated recompute work, and a swap round-trip
        loses none."""
        if self.faults is not None \
                and self.faults.take_swap_fault(self._step_index):
            raise SwapFault(
                f"injected swap-out failure at step {self._step_index}")
        phase = self._phase_of(req)
        n, nbytes = self.kv.swap_out(req.rid)
        (self.prefilling if phase == "prefill"
         else self.decoding).remove(req)
        m = self.metrics
        m.counter("engine.swap.out").inc()
        m.counter("engine.swap.bytes").inc(nbytes)
        # commitment shrinks to what stays device-resident (the retained
        # shared pages); the host-resident entries re-commit at swap-in
        held = sum(1 for e in self.kv.tables[req.rid]
                   if isinstance(e, int))
        cur = self._committed[req.rid]
        self._committed[req.rid] = held
        self._committed_total += held - cur
        req.swapped = True
        req.n_swapped += 1
        req.swap_retries = 0
        req.not_before_step = \
            self._step_index + 2 ** min(req.n_swapped - 1, 5)
        if self.tracer:
            self.tracer.end(phase, pid=PID_REQUESTS, tid=req.rid)
            self.tracer.instant("swapped_out", pid=PID_REQUESTS,
                                tid=req.rid,
                                args={"pages": n, "bytes": nbytes})
            self.tracer.begin("queued", pid=PID_REQUESTS, tid=req.rid)
        self.queue.insert(0, req)

    def _try_swap_in(self, req: EngineRequest) -> bool:
        """Re-admission transfer for a swapped-out request: allocate
        device pages (evicting cached prefixes under pressure), copy the
        host pages back, patch the block table in place. On an injected
        `SwapFault` or a genuine allocation failure the attempt retries
        with exponential backoff up to `swap_max_retries`, then degrades
        to recompute-by-replay."""
        m = self.metrics
        try:
            if self.faults is not None \
                    and self.faults.take_swap_fault(self._step_index):
                raise SwapFault(
                    f"injected swap-in failure at step {self._step_index}")
            n, nbytes = self.kv.swap_in(req.rid, self._alloc_pages)
        except (SwapFault, MemoryError) as e:
            req.swap_retries += 1
            if req.swap_retries > self.swap_max_retries:
                self._fallback_to_replay(req, why=str(e))
            else:
                m.counter("engine.swap.retries").inc()
                req.not_before_step = \
                    self._step_index + 2 ** (req.swap_retries - 1)
            return False
        m.counter("engine.swap.in").inc()
        m.counter("engine.swap.bytes").inc(nbytes)
        held = len(self.kv.tables[req.rid])
        cur = self._committed[req.rid]
        self._committed[req.rid] = held
        self._committed_total += held - cur
        req.swapped = False
        req.swap_retries = 0
        return True

    def _fallback_to_replay(self, req: EngineRequest, *, why: str):
        """Degrade a swapped-out queued request to PR 8 recompute-by-
        replay: drop its host copy and residual device references, reset
        the cached state, and let the normal replay admission path
        re-prefill it — bounded by `max_preemptions` like any
        preemption (the recompute bound applies the moment recompute
        work actually becomes necessary)."""
        m = self.metrics
        m.counter("engine.swap.fallbacks").inc()
        self._release(req)
        req.swapped = False
        req.swap_retries = 0
        req.n_cached = 0
        req.next_token = None
        req.n_preempted += 1
        m.counter("engine.preemptions").inc()
        if self.tracer:
            self.tracer.instant("swap_fallback", pid=PID_REQUESTS,
                                tid=req.rid, args={"why": why})
        if req.n_preempted > self.max_preemptions:
            req.failed = (f"swap-in abandoned ({why}); preempted "
                          f"{req.n_preempted} times "
                          f"(max_preemptions={self.max_preemptions})")
            self._terminate(req, "failed")
        else:
            req.not_before_step = self._step_index + 1

    def _reclaim(self):
        """Page pressure ladder: cached prefixes are speculative capacity,
        live sequences are real work — evict from the radix tree first
        and only preempt a victim when the tree has nothing unpinned left
        to give."""
        if self.prefix_cache is not None \
                and self.prefix_cache.evict(max(1, self.max_seqs)):
            return
        self._handle_exhaustion()

    def _handle_exhaustion(self):
        """The page pool exhausted mid-growth: pick the best victim —
        fewest generated tokens (least work lost), latest-admitted
        breaking ties — among active requests that actually hold pages,
        then either swap its exclusive pages to the host tier (when the
        cost rule says the bytes beat the replay) or preempt it for
        recompute-by-replay. An injected swap fault falls back to the
        replay arm for the same victim."""
        holders = [r for r in self.active if self.kv.tables.get(r.rid)]
        if not holders:
            # every held page belongs to swapped-out queue entries
            # (retained shared pages) — or the books really are broken:
            # degrade one swapped request to a full replay, freeing its
            # residual references, and let the grower retry
            for r in self.queue:
                if r.swapped:
                    self._fallback_to_replay(
                        r, why="page pool exhausted with no active holder")
                    return
            alloc = self.kv.allocator
            raise EngineStalledError(
                "page pool exhausted but no active request holds pages — "
                f"allocator books are broken (capacity {alloc.capacity}, "
                f"free {alloc.n_free}, committed {self._committed_total})")
        victim = min(holders,
                     key=lambda r: (len(r.generated), -r.admit_seq))
        if self._should_swap(victim):
            try:
                self._swap_out(victim)
                return
            except SwapFault:
                self.metrics.counter("engine.swap.fallbacks").inc()
        self._preempt(victim)

    def _check_stalled(self):
        """Raise a diagnosable error when head-of-line demand can never
        be satisfied: nothing is active (so no pages will ever free up)
        and an admission-eligible request is still blocked."""
        if self.active or not self.queue:
            return
        eligible = [r for r in self.queue
                    if r.not_before_step <= self._step_index]
        if not eligible:
            return        # every entry is in replay backoff; steps advance
        alloc = self.kv.allocator
        who = "; ".join(
            f"rid {r.rid} needs {self._pages_needed(r)} pages"
            for r in eligible)
        raise EngineStalledError(
            "scheduler stalled: no active sequences and admission cannot "
            f"proceed (capacity {alloc.capacity}, free {alloc.n_free}, "
            f"committed {self._committed_total}, headroom "
            f"{self.headroom_pages if self.admission == 'optimistic' else 0}"
            f"); blocked: {who}")

    def _fused(self, name: str, impl, variant: tuple, shape: tuple):
        """One fused device dispatch per phase: forward (page writes +
        table walk inside) → sample trace into a single jit'd call, so
        per-step host overhead stays flat as the model grows. The pool is
        donated — a pool sized to fill HBM must not need a second copy
        live across the in-place page update. Compiled once per (phase,
        kernels-enabled, variant) triple with the flag re-pinned inside
        the traced body, so `use_kernels(...)` scopes keep selecting the
        path they request instead of replaying the first-traced one;
        `variant` names host-known static choices (e.g. `("filtered",)`
        when a row needs top-k/p filtering), and the program is named
        `engine_<phase>[_<variant>...]` on the device. `shape` holds the
        dispatch's argument dimensions that vary (every other argument
        shape is fixed for the engine's life). Returns the jitted program
        and whether this (program, shape) signature is new to the engine,
        i.e. the dispatch traces and then compiles or loads; new ones are
        counted in `engine.dispatch.new_shapes`."""
        key = (name, kops.kernels_enabled(), variant)
        fn = self._jit_cache.get(key)
        if fn is None:
            enabled = key[1]

            def wrapped(*args):
                with kops.use_kernels(enabled):
                    return impl(*args)

            wrapped.__name__ = wrapped.__qualname__ = "_".join(
                ("engine", name) + variant)
            fn = self._jit_cache[key] = jax.jit(wrapped, donate_argnums=(0,))
        new = (key, shape) not in self._dispatched
        if new:
            self._dispatched.add((key, shape))
            self.metrics.counter("engine.dispatch.new_shapes").inc()
        return fn, new

    def _span(self, name: str, annotation=jax.profiler.TraceAnnotation,
              **args):
        """A step phase as a host span on the profiler's clock (nearly
        free while no profiler runs); with a `Tracer` attached, also on
        its engine track, with the step number. Phase-level only: never
        per request or row."""
        if self.tracer is None:
            return annotation(name, **args)
        return self._traced_span(name, annotation, args)

    @contextlib.contextmanager
    def _traced_span(self, name, annotation, args):
        with annotation(name, **args) as ann, self.tracer.span(
                name, args={"step_num": self._step_index, **args}):
            yield ann

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------

    def _register_metrics(self):
        """Pre-create every engine instrument so a snapshot is schema-
        complete (`serve.telemetry.schema`) even before any traffic."""
        m = self.metrics
        for name in ("engine.steps", "engine.prefill_tokens",
                     "engine.decode_tokens", "engine.generated_tokens",
                     "engine.pages_walked", "engine.pages_walked_dense",
                     "engine.requests.submitted", "engine.requests.admitted",
                     "engine.requests.finished", "engine.requests.stop_hits",
                     "engine.requests.cancelled", "engine.requests.expired",
                     "engine.requests.failed", "engine.preemptions",
                     "engine.replayed_prefill_tokens",
                     "engine.dispatch.faults", "engine.admission.blocked",
                     "engine.prefix.hits", "engine.prefix.misses",
                     "engine.prefix.hit_tokens", "engine.prefix.cow_copies",
                     "engine.prefix.inserted_pages",
                     "engine.prefix.evicted_pages",
                     "engine.swap.out", "engine.swap.in",
                     "engine.swap.bytes", "engine.swap.retries",
                     "engine.swap.fallbacks", "engine.requests.poisoned",
                     "engine.stream.callback_errors",
                     "engine.dispatch.new_shapes"):
            m.counter(name)
        for name in ("engine.step.wall_s", "engine.step.host_s",
                     "engine.step.budget_utilization",
                     "engine.decode.batch_occupancy",
                     "engine.decode.token_latency_s",
                     "engine.admission.wait_s", "engine.request.e2e_s",
                     "engine.prefill.chunk_tokens"):
            m.histogram(name)
        self._update_gauges()

    def _update_gauges(self):
        """Refresh the level gauges from the live bookkeeping."""
        m = self.metrics
        alloc = self.kv.allocator
        m.gauge("engine.pages.capacity").set(alloc.capacity)
        m.gauge("engine.pages.in_use").set(alloc.in_use)
        m.gauge("engine.pages.peak_in_use").set(alloc.peak_in_use)
        m.gauge("engine.pages.utilization").set(
            alloc.in_use / max(alloc.capacity, 1))
        m.gauge("engine.pages.utilization_peak").set(
            alloc.peak_in_use / max(alloc.capacity, 1))
        m.gauge("engine.pages.reserved").set(self._committed_total)
        m.gauge("engine.pages.scrubbed").set(self.kv.pages_scrubbed)
        m.gauge("engine.queue.depth").set(len(self.queue))
        m.gauge("engine.batch.decoding").set(len(self.decoding))
        m.gauge("engine.batch.prefilling").set(len(self.prefilling))
        m.gauge("engine.pages.shared").set(alloc.n_shared)
        tree = self.prefix_cache
        m.gauge("engine.prefix.tree_pages").set(
            tree.n_pages if tree is not None else 0)
        m.gauge("engine.prefix.tree_nodes").set(
            tree.n_nodes if tree is not None else 0)
        self._sync_prefix_counters()
        # host-tier occupancy: always emitted (zeros when no pool) so the
        # snapshot shape is policy-independent
        hp = self.kv.host_pool
        pb = self.kv.page_bytes
        m.gauge("engine.swap.host_pages").set(hp.in_use if hp else 0)
        m.gauge("engine.swap.host_pages_capacity").set(
            hp.capacity if hp else 0)
        m.gauge("engine.swap.host_bytes").set(hp.in_use * pb if hp else 0)
        m.gauge("engine.swap.host_budget_bytes").set(
            hp.capacity * pb if hp else 0)
        regs = self.kv.registers
        if regs is not None:
            m.gauge("engine.register_slots.capacity").set(regs.capacity)
            m.gauge("engine.register_slots.in_use").set(regs.in_use)
            m.gauge("engine.register_slots.peak_in_use").set(
                regs.peak_in_use)
            m.gauge("engine.register_slots.scrubbed").set(
                self.kv.slots_scrubbed)

    def _sync_prefix_counters(self):
        """The tree counts its own insert/evict traffic; mirror it as
        monotonic counters (same pattern as the kernel dispatch tallies in
        metrics_snapshot), live at every step boundary."""
        tree = self.prefix_cache
        if tree is None:
            return
        m = self.metrics
        for name, n in (("engine.prefix.inserted_pages", tree.inserted_pages),
                        ("engine.prefix.evicted_pages", tree.evicted_pages)):
            c = m.counter(name)
            if n > c.value:
                c.value = n

    def metrics_snapshot(self) -> dict:
        """Schema-versioned registry export (the shape
        `serve.telemetry.schema.validate_snapshot` checks): refresh the
        level gauges, mirror the kernel layer's per-entry-point dispatch
        tallies, and snapshot."""
        self._update_gauges()
        for (entry, path), n in kops.dispatch_counts().items():
            c = self.metrics.counter(f"kernels.dispatch.{entry}.{path}")
            if n > c.value:
                c.value = n   # mirror of an external monotonic count
        return self.metrics.snapshot()

    def reset_metrics(self):
        """Start a fresh measurement window: zero the registry in place
        (names and held instrument references survive), restart the
        allocator high-water marks and scrub totals, and clear the
        kernel dispatch tallies and the probe sampling phase. Engine
        *state* (queues, caches, PRNG seed, step index) is untouched —
        this is the boundary the benches put between warm-up and the
        timed run."""
        self.metrics.reset()
        self.kv.allocator.reset_peak()
        if self.kv.registers is not None:
            self.kv.registers.reset_peak()
        self.kv.pages_scrubbed = 0
        self.kv.slots_scrubbed = 0
        if self.prefix_cache is not None:
            # cached *contents* survive the window boundary (they are
            # state, not measurement); only the traffic stats restart
            self.prefix_cache.inserted_pages = 0
            self.prefix_cache.evicted_pages = 0
        kops.reset_dispatch_counts()
        if self.quality_probes is not None:
            self.quality_probes.reset()
        self._update_gauges()

    def check_books(self):
        """Assert the accounting invariants the chaos tests lean on:
        the running committed total matches the per-rid map, every
        committed rid is active, and allocator free + in-use cover the
        capacity exactly. Cheap enough to call after every step."""
        assert self._committed_total == sum(self._committed.values()), \
            (self._committed_total, self._committed)
        active = {r.rid for r in self.active}
        swapped = {r.rid for r in self.queue if r.swapped}
        assert not (active & swapped), (active, swapped)
        assert set(self._committed) == active | swapped \
            == set(self.kv.tables), \
            (set(self._committed), active, swapped, set(self.kv.tables))
        # a swapped rid's commitment covers exactly its device-resident
        # (retained shared) entries — host residency is not pool demand
        for r in self.queue:
            if r.swapped:
                assert self._committed[r.rid] == sum(
                    1 for e in self.kv.tables[r.rid] if isinstance(e, int))
        # quiescent between ops: no page may be stuck mid-transfer
        assert not self.kv._inflight, self.kv._inflight
        # host-tier books: slots in use == host-resident table entries,
        # each referenced exactly once (host pages are never shared)
        host_refs = [e.slot for t in self.kv.tables.values()
                     for e in t if not isinstance(e, int)]
        hp = self.kv.host_pool
        if hp is not None:
            assert hp.in_use == len(host_refs) == len(set(host_refs)), \
                (hp.in_use, host_refs)
        else:
            assert not host_refs, host_refs
        alloc = self.kv.allocator
        # sharing-aware: a page may appear in several tables *and* the
        # radix tree, but occupies the pool once — and its refcount must
        # equal exactly that multiplicity (tree membership counts once)
        counts: dict[int, int] = {}
        for t in self.kv.tables.values():
            for p in t:
                if isinstance(p, int):
                    counts[p] = counts.get(p, 0) + 1
        if self.prefix_cache is not None:
            tree_pages = self.prefix_cache.held_pages()
            assert len(tree_pages) == self.prefix_cache.n_pages, \
                (len(tree_pages), self.prefix_cache.n_pages)
            for p in tree_pages:
                counts[p] = counts.get(p, 0) + 1
        assert alloc.in_use == len(counts), (alloc.in_use, len(counts))
        for p, c in counts.items():
            assert alloc.refcount(p) == c, (p, alloc.refcount(p), c)
        assert alloc.n_free + alloc.in_use == alloc.capacity
        if self.kv.registers is not None:
            assert self.kv.registers.in_use == len(self.kv.slots)

    def _ensure(self, rid: int, n_tokens: int):
        """`kv.ensure` plus the optimistic growth-commit update, the
        fault-injection hook, and an instant trace event when the growth
        actually allocated pages."""
        table = self.kv.tables[rid]
        need = pages_for(n_tokens, self.kv.page_size) - len(table)
        if need > 0 and self.faults is not None \
                and any(self.kv.tables.get(r.rid) for r in self.active) \
                and self.faults.take_exhaustion(self._step_index):
            # only inject once a victim exists — a real allocator can't
            # exhaust while zero pages are held
            raise MemoryError(
                f"injected page exhaustion at step {self._step_index}")
        if self.tracer is None:
            self.kv.ensure(rid, n_tokens)
        else:
            before = self.kv.allocator.n_free
            self.kv.ensure(rid, n_tokens)
            got = before - self.kv.allocator.n_free
            if got:
                self.tracer.instant("alloc_pages", pid=PID_REQUESTS, tid=rid,
                                    args={"pages": got})
        # commitment follows real growth (no-op under "reserve", whose
        # worst-case commitment always covers the table)
        held = len(table)
        cur = self._committed[rid]
        if held > cur:
            self._committed[rid] = held
            self._committed_total += held - cur

    # -- back-compat counter views (the registry owns the numbers) -----

    @property
    def n_steps(self) -> int:
        return self.metrics.counter("engine.steps").value

    @property
    def n_prefill_tokens(self) -> int:
        return self.metrics.counter("engine.prefill_tokens").value

    @property
    def n_decode_tokens(self) -> int:
        return self.metrics.counter("engine.decode_tokens").value

    @property
    def pages_walked(self) -> int:
        return self.metrics.counter("engine.pages_walked").value

    @property
    def pages_walked_dense(self) -> int:
        return self.metrics.counter("engine.pages_walked_dense").value

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    @staticmethod
    def _check_stop(req: EngineRequest):
        for seq in req.sampling.stop:
            n = len(seq)
            if len(req.generated) >= n and req.generated[-n:] == list(seq):
                req.stop_hit = True
                return

    @staticmethod
    def _wants_filtering(batch) -> bool:
        return any(r.sampling.top_k > 0 or r.sampling.top_p < 1.0
                   for r in batch)

    def _grow_decode(self):
        """Grow every decoding sequence's table by one position,
        preempting victims until growth fits (ensure is idempotent, so
        the retry loop re-runs cheaply after each preemption)."""
        if not self.spec.kv:
            return
        while True:
            try:
                for req in list(self.decoding):
                    self._ensure(req.rid, req.n_cached + 1)
                return
            except MemoryError:
                self._reclaim()

    def _decode_impl(self, state, params, base, bt, reg, tokens, fill, lens,
                     rids, temps, top_ks, top_ps, *, filtered, probe=False):
        # block-table-native: the forward writes each new KV row into its
        # page and attends by walking `bt` — no gathered slab exists.
        # `lens` are the true per-slot context lengths (0 for padded
        # rows): the kernel's ragged early-exit walks only each
        # sequence's live pages instead of every table column. `reg` is
        # each row's register slot (scratch for padded rows) for models
        # whose spec carries fixed-size state. Sampling keys derive from
        # (rid, lens) — lens IS the sampled token's stream position — so
        # a replayed request resamples identically. The probe variant
        # (its own compiled executable via the jit-cache variant key)
        # additionally returns the barrier-isolated per-layer quality
        # stats — same dispatch shapes, same sampling keys.
        if probe:
            logits, state, stats = self.adapter.forward_chunk(
                params, tokens, state, fill, bt, lens, reg, probe=True)
        else:
            logits, state = self.adapter.forward_chunk(params, tokens, state,
                                                       fill, bt, lens, reg)
        lg = logits[:, 0].astype(jnp.float32)
        keys = _row_keys(base, rids, lens)
        toks = _sample_tokens(keys, lg, temps, top_ks, top_ps,
                              filtered=filtered)
        # per-row max logit: the host-side non-finite sentinel reads it
        # to flag poisoned rows (NaN/Inf adapter output) without pulling
        # the full logits matrix off device
        mx = jnp.max(lg, axis=-1)
        if probe:
            return state, lg, toks, mx, stats
        return state, lg, toks, mx

    def _decode_once(self) -> tuple[int, list[EngineRequest]]:
        """Grow every decoding table, dispatch the batch, wait for its
        sampled tokens and commit them; returns (rows dispatched,
        requests finished)."""
        m = self.metrics
        with self._span("engine.prepare.decode"):
            self._grow_decode()
            batch = list(self.decoding)
            if not batch:
                return 0, []
            b = self.max_seqs
            pad = b - len(batch)
            rids = [r.rid for r in batch] + [None] * pad
            new_lens = [r.n_cached + 1 for r in batch]
            n_cols = 0
            if self.spec.kv:
                n_cols = _next_pow2(max(
                    pages_for(n, self.kv.page_size) for n in new_lens))
                bt = self.kv.block_table_array(rids, n_cols)
                m.counter("engine.pages_walked").inc(
                    sum(pages_for(n, self.kv.page_size) for n in new_lens))
                m.counter("engine.pages_walked_dense").inc(b * n_cols)
            else:
                bt = None
            reg = self.kv.register_index_array(rids) if self.spec.register \
                else None
            args = (
                self.kv.state, self.adapter.params, self._base_key, bt, reg,
                jnp.asarray([[r.next_token] for r in batch] + [[0]] * pad,
                            jnp.int32),
                jnp.asarray([r.n_cached for r in batch] + [0] * pad,
                            jnp.int32),
                jnp.asarray(new_lens + [0] * pad, jnp.int32),
                jnp.asarray([r.rid for r in batch] + [0] * pad, jnp.int32),
                jnp.asarray([r.sampling.temperature for r in batch]
                            + [0.0] * pad, jnp.float32),
                jnp.asarray([r.sampling.top_k for r in batch] + [0] * pad,
                            jnp.int32),
                jnp.asarray([r.sampling.top_p for r in batch] + [1.0] * pad,
                            jnp.float32))
            filtered = self._wants_filtering(batch)
            probe = (self.quality_probes is not None
                     and self.quality_probes.should_probe())
            m.histogram("engine.decode.batch_occupancy").observe(
                len(batch) / self.max_seqs)
            fn, new = self._fused(
                "decode",
                functools.partial(self._decode_impl, filtered=filtered,
                                  probe=probe),
                tuple(n for n, on in (("filtered", filtered), ("probe", probe))
                      if on), (n_cols,))
        with self._span("engine.dispatch.decode", rows=len(batch),
                        n_cols=n_cols, new_shape=new):
            out = fn(*args)
        if probe:
            self.kv.state, logits, toks, mx, stats = out
        else:
            (self.kv.state, logits, toks, mx), stats = out, None
        with self._span("engine.wait.decode"):
            t = time.perf_counter()
            toks = np.asarray(toks)
            finite = np.isfinite(np.asarray(mx))
            if stats is not None:
                self.quality_probes.record(stats)
            self._waited += time.perf_counter() - t
        with self._span("engine.commit.decode"):
            finished = []
            for i, req in enumerate(batch):
                if not finite[i]:
                    # poisoned adapter output (NaN/Inf logits): terminate
                    # only this row — its sampled token is garbage and
                    # must not enter the stream; other rows are
                    # independent
                    req.failed = (f"non-finite logits at stream position "
                                  f"{req.n_cached} (poisoned model output)")
                    m.counter("engine.requests.poisoned").inc()
                    self._terminate(req, "failed")  # returned via _terminal
                    continue
                req.n_cached += 1
                req.generated.append(int(toks[i]))
                req.next_token = int(toks[i])
                if self.record_logits:
                    req.step_logits.append(np.asarray(logits[i], np.float32))
                m.counter("engine.decode_tokens").inc()
                m.counter("engine.generated_tokens").inc()
                self._check_stop(req)
                if req.done:
                    self.decoding.remove(req)
                    self._finish(req)
                    finished.append(req)
        return len(batch), finished

    # ------------------------------------------------------------------
    # chunked prefill
    # ------------------------------------------------------------------

    def _prefill_impl(self, state, params, base, bt, reg, tokens, start,
                      last, lens, rids, temp, top_k, top_p, *, filtered):
        # padded tail rows are computed too (their queries may attend the
        # garbage keys the same forward wrote for earlier padding tokens,
        # so their outputs are meaningless and discarded); their in-page
        # writes land on the scratch page or on not-yet-valid slots that
        # are rewritten before the causal mask ever exposes them. `lens`
        # is the true cached length after this chunk (start + real): the
        # kernel's early-exit trims the walk to the live pages, which
        # also stops the padded tail queries from touching columns past
        # them (their outputs are discarded either way), and — via
        # valid_len = lens - start inside the model — keeps the padded
        # tail out of register-kind (SSM) carried state, whose update is
        # a recurrence rather than a masked read. `lens` doubles as the
        # sampled token's stream position for the (rid, position) key.
        logits, state = self.adapter.forward_chunk(params, tokens, state,
                                                   start, bt, lens, reg)
        lg = jax.lax.dynamic_index_in_dim(logits, last, axis=1,
                                          keepdims=False)[0]
        lg = lg.astype(jnp.float32)
        keys = _row_keys(base, rids, lens)
        tok = _sample_tokens(keys, lg[None], temp, top_k, top_p,
                             filtered=filtered)[0]
        # max logit of the sampled row, for the non-finite sentinel
        return state, lg, tok, jnp.max(lg)

    def _prefill_once(self, budget: int) -> tuple[int, list[EngineRequest]]:
        """Advance the head-of-line prefill by up to `budget` tokens of
        its stream (the prompt, plus already-generated tokens when
        replaying a preempted request); returns (tokens consumed,
        requests finished)."""
        req = self.prefilling[0]
        stream = self._stream(req)
        start = req.n_cached
        m = self.metrics
        real = min(self.prefill_chunk, budget, len(stream) - start)
        padded = _next_pow2(real)
        with self._span("engine.prepare.prefill"):
            n_cols = 0
            if self.spec.kv:
                while True:
                    try:
                        self._ensure(req.rid, start + real)
                        break
                    except MemoryError:
                        self._reclaim()
                        if req not in self.prefilling:
                            return 0, []   # the head itself was preempted
                n_cols = _next_pow2(pages_for(start + padded,
                                              self.kv.page_size))
                bt = self.kv.block_table_array([req.rid], n_cols)
                m.counter("engine.pages_walked").inc(
                    pages_for(start + real, self.kv.page_size))
                m.counter("engine.pages_walked_dense").inc(n_cols)
            else:
                bt = None
            reg = self.kv.register_index_array([req.rid]) \
                if self.spec.register else None
            self._maybe_dispatch_fault("prefill")

            # every device-side shape depends only on (padded, n_cols),
            # both powers of two, so prefill compiles a bounded set of
            # variants; `last` (= real - 1) rides along as a traced scalar
            chunk = stream[start:start + real] + [0] * (padded - real)
            args = (
                self.kv.state, self.adapter.params, self._base_key, bt, reg,
                jnp.asarray([chunk], jnp.int32), jnp.asarray(start, jnp.int32),
                jnp.asarray(real - 1, jnp.int32),
                jnp.asarray([start + real], jnp.int32),
                jnp.asarray([req.rid], jnp.int32),
                jnp.asarray([req.sampling.temperature], jnp.float32),
                jnp.asarray([req.sampling.top_k], jnp.int32),
                jnp.asarray([req.sampling.top_p], jnp.float32))
            filtered = self._wants_filtering([req])
            fn, new = self._fused(
                "prefill",
                functools.partial(self._prefill_impl, filtered=filtered),
                ("filtered",) if filtered else (), (padded, n_cols))
        with self._span("engine.dispatch.prefill", tokens=real,
                        padded=padded, n_cols=n_cols, new_shape=new):
            self.kv.state, last, tok, mx = fn(*args)
        complete = start + real == len(stream)
        with self._span("engine.wait.prefill"):
            t = time.perf_counter()
            mx = float(mx)
            if complete:
                tok = int(tok)
            self._waited += time.perf_counter() - t
        with self._span("engine.commit.prefill"):
            if not np.isfinite(mx):
                # poisoned adapter output mid-prefill: the chunk's logits
                # are garbage, so the request cannot continue — terminate
                # it alone (the single-sequence dispatch touched no other
                # state)
                req.failed = (f"non-finite logits in prefill at stream "
                              f"position {start + real - 1} "
                              f"(poisoned model output)")
                m.counter("engine.requests.poisoned").inc()
                self._terminate(req, "failed")
                return real, []

            req.n_cached = start + real
            m.counter("engine.prefill_tokens").inc(real)
            if req.n_preempted > 0:
                # replay cost = rows actually recomputed (a prefix-tree hit
                # at re-admission already skipped the resident ones)
                m.counter("engine.replayed_prefill_tokens").inc(real)
            m.histogram("engine.prefill.chunk_tokens").observe(real)
            finished = []
            if complete:
                # stream fully cached: the fused call already sampled the
                # next token from the last real position's logits (for a
                # replay, this continues the original sequence exactly —
                # the (rid, position) key is the one the undisturbed
                # decode used)
                self.prefilling.remove(req)
                req.generated.append(tok)
                req.next_token = tok
                if self.record_logits:
                    req.step_logits.append(np.asarray(last, np.float32))
                m.counter("engine.generated_tokens").inc()
                if self.tracer:
                    self.tracer.end("prefill", pid=PID_REQUESTS, tid=req.rid)
                    self.tracer.begin("decode", pid=PID_REQUESTS,
                                      tid=req.rid)
                self._check_stop(req)
                if req.done:
                    self._finish(req)
                    finished.append(req)
                else:
                    self.decoding.append(req)
        return real, finished

    # ------------------------------------------------------------------
    # driver
    # ------------------------------------------------------------------

    def step(self) -> list[EngineRequest]:
        """One engine iteration; returns every request that reached a
        terminal state during it (completed, cancelled, expired, or
        failed — check `req.outcome`)."""
        m = self.metrics
        t0 = time.perf_counter()
        self._waited = 0.0
        gen0 = m.counter("engine.generated_tokens").value
        with self._span("engine.step", jax.profiler.StepTraceAnnotation,
                        step_num=self._step_index) as span:
            with self._span("engine.admit"):
                self._apply_faults()
                self._expire_deadlines()
                self._admit()
                self._check_stalled()
            finished = []
            budget = self.token_budget
            spent = rows = chunks = 0
            if self.decoding:
                try:
                    self._maybe_dispatch_fault("decode")
                    rows, fin = self._decode_once()
                    budget -= rows
                    spent += rows
                    finished.extend(fin)
                except DispatchFault:
                    m.counter("engine.dispatch.faults").inc()
            # `guard` bounds the zero-progress retries a preempted prefill
            # head can cause within one step (each retry strictly shrinks
            # the active set, so this terminates regardless)
            guard = len(self.prefilling) + 1
            while budget > 0 and self.prefilling and guard > 0:
                try:
                    used, fin = self._prefill_once(budget)
                except DispatchFault:
                    m.counter("engine.dispatch.faults").inc()
                    break
                budget -= used
                spent += used
                finished.extend(fin)
                if used == 0:
                    guard -= 1
                else:
                    chunks += 1
            with self._span("engine.account"):
                m.counter("engine.steps").inc()
                wall = time.perf_counter() - t0
                m.histogram("engine.step.wall_s").observe(wall)
                m.histogram("engine.step.host_s").observe(wall - self._waited)
                m.histogram("engine.step.budget_utilization").observe(
                    spent / self.token_budget)
                # each token generated this step inherits the step's wall
                # time (the host read of the sampled tokens already forced
                # the device sync, so the wall is real)
                lat = m.histogram("engine.decode.token_latency_s")
                for _ in range(m.counter("engine.generated_tokens").value
                               - gen0):
                    lat.observe(wall)
                self._sync_prefix_counters()
                finished.extend(self._terminal)
                self._terminal.clear()
            with self._span("engine.stream"):
                self._flush_streams(finished)
            span.set_metadata(rows=rows, chunks=chunks)
        self._step_index += 1
        return finished

    def _flush_streams(self, finished: list[EngineRequest]):
        """Step-boundary streaming: deliver every not-yet-streamed
        generated token to its request's `on_token` callback. Runs after
        all device work and bookkeeping for the step, so callbacks
        observe a consistent engine and cannot perturb the step that
        produced their tokens. Terminal requests' callbacks are dropped
        after their final flush. A *raising* callback is caught per
        callback — counted (`engine.stream.callback_errors`) and dropped
        so one broken consumer can never abort delivery to the other
        streams or propagate out of `step()` after bookkeeping."""
        if not self._callbacks:
            return
        for req in self.active + finished:
            cb = self._callbacks.get(req.rid)
            if cb is None:
                continue
            while req.n_streamed < len(req.generated):
                tok = req.generated[req.n_streamed]
                req.n_streamed += 1
                try:
                    cb(req.rid, tok)
                except Exception:
                    self.metrics.counter(
                        "engine.stream.callback_errors").inc()
                    self._callbacks.pop(req.rid, None)
                    break
        for req in finished:
            self._callbacks.pop(req.rid, None)

    def run(self) -> list[EngineRequest]:
        done = []
        while self.queue or self.active:
            done.extend(self.step())
        done.extend(self._terminal)   # cancels issued between steps
        self._terminal.clear()
        self._flush_streams(done)
        return done

    def drain(self) -> list[EngineRequest]:
        """Graceful shutdown: stop admitting — queued requests that were
        never admitted terminate `cancelled` — finish every piece of
        in-flight work (active sequences, parked replays, swapped-out
        residents: all of it represents admitted work the engine owes an
        answer for), then assert balanced books and zero non-scratch
        residency on every tier. Returns the requests that reached a
        terminal state during the drain. The engine stays draining
        afterwards: further `submit()` calls are rejected."""
        self._draining = True
        for req in list(self.queue):
            if req.t_admit is None:
                # never admitted — no partial work to honor
                self.cancel(req.rid)
        done = []
        while self.queue or self.active:
            done.extend(self.step())
        done.extend(self._terminal)
        self._terminal.clear()
        self._flush_streams(done)
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        self.check_books()
        alloc = self.kv.allocator
        assert alloc.in_use == 0 and alloc.n_free == alloc.capacity, \
            f"device pages leaked: {alloc.in_use} still in use"
        assert not self.kv.tables and not self.kv.slots, \
            (self.kv.tables, self.kv.slots)
        assert not self._committed and self._committed_total == 0, \
            (self._committed, self._committed_total)
        hp = self.kv.host_pool
        assert hp is None or hp.in_use == 0, \
            f"host tier leaked: {hp.in_use} slots still in use"
        regs = self.kv.registers
        assert regs is None or regs.in_use == 0, \
            f"register slots leaked: {regs.in_use} still in use"
        return done
