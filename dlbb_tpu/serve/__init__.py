"""Serving subsystem: continuous-batching inference over a paged,
mesh-sharded KV-cache, driven by synthetic traffic traces.  Three boxes,
imports one way (scheduler -> a family's programs -> cache helpers):

- ``engine.py``  — the scheduler: ``ServingEngine`` (admission control,
  bounded queue, chunked prefill interleaved with fused decode scans, an
  in-flight dispatch window, retries and the watchdog) and
  ``family_for``, the one place that picks a block family;
- ``gpt.py`` / ``hybrid.py`` — a block family's jitted device programs
  (prefill chunk, decode step and fused ladder, inject) and what the
  scheduler asks of a family; ``gpt.py`` also holds the speculative
  programs, ``hybrid.py`` the recurrent-state mixers;
- ``kvcache.py`` — the cache pytrees (slot dim over dp, kv-head dim over
  tp, GQA-aware) + host block ledger (alloc/free/append accounting);
  ``attend.py`` — the layer views and dense attentions over cached keys
  both families read the cache with;
- ``config.py``  — ``ServingConfig``, the envelope and its validation;
  ``speculative.py`` — the host-side numpy of drafting and sampling;
- ``traffic.py`` — seeded, replayable arrival processes (Poisson /
  bursty MMPP / diurnal) with sampled prompt/output lengths;
- ``bench.py``   — the trace-driven harness behind ``cli serve``
  (atomic report JSON + manifest + metrics.prom + journal);
  ``fleet.py`` — replicas of the engine behind a router.

Import each name from the module that holds it: the package imports
nothing, so that a family's programs load without the scheduler.  See
``docs/serving.md`` for the architecture, cache sharding contract, trace
schema, and report fields.
"""
