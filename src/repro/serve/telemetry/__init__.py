"""Serve-time telemetry: metrics, request tracing, and rotation-quality
probes for the paged serving engine.

Dependency-free by construction — `metrics` and `trace` are stdlib-only
(importable from the scheduler hot loop, the benches, or a bare
telemetry shard); `quality` uses jax only for the probe math that runs
inside the served forward. Nothing here changes what the engine
computes: tracing and probes are off by default, and turning them on is
bit-path-neutral (no dispatch-shape or PRNG-key effects — enforced by
the engine parity tests).

Metric taxonomy (schema version {ver}; canonical list + validation in
`schema.py`, exported snapshots are stamped and checked against it):

* ``engine.*`` — scheduler/engine signals.
  Counters: ``engine.steps``, ``engine.prefill_tokens``,
  ``engine.decode_tokens``, ``engine.generated_tokens`` (decode tokens
  plus each request's prefill-sampled first token),
  ``engine.pages_walked`` / ``engine.pages_walked_dense`` (ragged
  early-exit vs padded full-table walk, per attention dispatch),
  ``engine.requests.{{submitted,admitted,finished,stop_hits}}``,
  ``engine.admission.blocked`` (head-of-line blocked on pages),
  ``engine.dispatch.new_shapes`` (dispatches of a program signature the
  engine had not dispatched before: each traces, then compiles or
  loads).
  Gauges: ``engine.pages.{{capacity,in_use,peak_in_use,reserved,
  scrubbed}}`` (allocator levels + high-water mark + scrub total),
  ``engine.register_slots.*`` (same, SSM/hybrid specs only),
  ``engine.queue.depth``, ``engine.batch.{{decoding,prefilling}}``.
  Gauges are refreshed from the live bookkeeping when a snapshot is
  taken, not every step.
  Histograms: ``engine.step.wall_s``, ``engine.step.host_s`` (the step
  wall minus its device waits: the host's own time),
  ``engine.step.budget_utilization`` (tokens spent / token budget),
  ``engine.decode.batch_occupancy`` (decode rows / max_seqs, observed
  per decode dispatch), ``engine.decode.token_latency_s`` (each
  generated token inherits its engine step's wall time),
  ``engine.admission.wait_s`` (submit → admission),
  ``engine.request.e2e_s`` (submit → finish),
  ``engine.prefill.chunk_tokens`` (real tokens per prefill dispatch).
* ``kernels.dispatch.<entry>.<kernels|ref>`` — per-entry-point dispatch
  tallies mirrored from `repro.kernels.ops` at snapshot time. These
  count *Python-level* calls: once per jit trace for traced callers,
  once per call for eager ones — the path tag records which backend the
  trace baked in (device time of the fused serving programs,
  `engine_decode*` / `engine_prefill*`, lives in a `jax.profiler` trace
  beside the engine's step-phase spans).
* ``quality.*`` — rotation-quality probes (int4 path, sampled every K
  decode dispatches): ``quality.<stat>`` pooled histograms and
  ``quality.layer<NN>.<stat>`` per-layer latest-value gauges for
  ``l1_imbalance_pre/post`` (max/mean blockwise ℓ1 mass, the paper's
  Theorem quantity), ``sat_rate`` (int4 codes pinned at the grid ends),
  and ``kurtosis_pre/post``; plus the ``quality.probe_dispatches``
  counter.

Snapshots are versioned dicts (`MetricsRegistry.snapshot()`), mergeable
across processes (`merge`: counters add, histogram buckets add) for the
multi-host roll-up. Traces (request lifecycles and the engine's step
phases) are Chrome Trace Event Format JSON that opens
directly in Perfetto (`Tracer.save`). `python -m
repro.serve.telemetry.check` validates both artifact kinds in CI.
"""
from .metrics import (SCHEMA_VERSION, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .quality import PROBE_STATS, QualityProbes, activation_probe_stats
from .schema import validate_snapshot
from .trace import PID_ENGINE, PID_REQUESTS, Tracer, validate_trace

__doc__ = __doc__.format(ver=SCHEMA_VERSION)

__all__ = [
    "SCHEMA_VERSION", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Tracer", "validate_trace", "PID_ENGINE", "PID_REQUESTS",
    "QualityProbes", "activation_probe_stats", "PROBE_STATS",
    "validate_snapshot",
]
