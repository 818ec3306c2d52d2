"""Engine-wide observability: request-lifecycle tracing, a metrics
registry, and runtime kernel-dispatch telemetry.

Three pieces, all off-by-default and cheap when off:

* ``obs.trace``   — a low-overhead span/event tracer (monotonic clocks,
  context-manager API) exporting Chrome trace-event JSON loadable in
  Perfetto.  ``PagedEngine`` emits per-tick spans and per-request
  lifecycle events (QUEUED -> ADMITTED [-> PREFIX_HIT] -> PREFILL ->
  DECODE -> PREEMPTED/requeued -> FINISHED, plus COW / PREFIX_PARKED /
  PREFIX_EVICT instants from the prefix-sharing subsystem and
  SPEC_ROLLBACK instants when speculative-decode rejection rewinds page
  growth); engine
  dispatches are additionally
  wrapped in ``jax.profiler.TraceAnnotation`` so XLA device profiles line
  up with the engine spans.
* ``obs.metrics`` — counters / gauges / log-bucket histograms with
  percentile summaries, JSON export (merged into ``PagedEngine.stats()``)
  and Prometheus text-format export for scrape-based deployments.
* ``obs.runmeta`` — run-metadata stamping (git sha, jax/device versions)
  for every BENCH_*.json the benchmark harness writes.

Kernel-dispatch telemetry lives in ``kernels.ops``: every dispatcher
records which path (``fused-tpu`` vs ``cpu-fallback``) it lowered per call
site into the default registry, so benchmark JSONs carry MEASURED dispatch
paths instead of a bench-side guess.

Metric-name reference
=====================

======================================  =========  =======  ==========================================
name                                    type       unit     emitting site
======================================  =========  =======  ==========================================
engine_ticks_total                      counter    ticks    serve/scheduler.py  PagedEngine.step
engine_dispatches_total                 counter    calls    serve/scheduler.py  PagedEngine._run_packed
engine_packed_calls_total               counter    calls    serve/scheduler.py  PagedEngine._step_packed
engine_prefill_tokens_total             counter    tokens   serve/scheduler.py  PagedEngine._run_packed
engine_decode_tokens_total              counter    tokens   serve/scheduler.py  PagedEngine._run_packed
engine_preemptions_total                counter    events   serve/scheduler.py  PagedEngine._preempt
engine_rejected_total                   counter    events   serve/scheduler.py  PagedEngine._reject
engine_admitted_total                   counter    events   serve/scheduler.py  PagedEngine._admit
engine_finished_total                   counter    events   serve/scheduler.py  PagedEngine._finish
engine_occupancy                        histogram  ratio    serve/scheduler.py  PagedEngine._run_packed
engine_tokens_per_dispatch              histogram  tokens   serve/scheduler.py  PagedEngine._run_packed
engine_padding_fraction                 histogram  ratio    serve/scheduler.py  PagedEngine._run_packed
engine_page_utilization                 histogram  ratio    serve/scheduler.py  PagedEngine.step
engine_queue_wait_ticks                 histogram  ticks    serve/scheduler.py  PagedEngine._admit
engine_ttft_ms                          histogram  ms       serve/scheduler.py  PagedEngine._run_packed
engine_ttft_ticks                       histogram  ticks    serve/scheduler.py  PagedEngine._run_packed
engine_inter_token_ms                   histogram  ms       serve/scheduler.py  PagedEngine._run_packed
engine_request_latency_ticks            histogram  ticks    serve/scheduler.py  PagedEngine._finish
engine_dispatch_ms                      histogram  ms       serve/scheduler.py  PagedEngine._run_packed
engine_cow_copies_total                 counter    pages    serve/scheduler.py  PagedEngine._ensure
engine_a1_sig_seeded_total              counter    events   serve/scheduler.py  PagedEngine._admit
engine_ttft_hit_ms                      histogram  ms       serve/scheduler.py  PagedEngine._run_packed
engine_ttft_cold_ms                     histogram  ms       serve/scheduler.py  PagedEngine._run_packed
engine_ttft_hit_ticks                   histogram  ticks    serve/scheduler.py  PagedEngine._run_packed
engine_ttft_cold_ticks                  histogram  ticks    serve/scheduler.py  PagedEngine._run_packed
engine_spec_accepted_total              counter    tokens   serve/scheduler.py  PagedEngine._consume_spec_lane
engine_spec_rejected_total              counter    tokens   serve/scheduler.py  PagedEngine._consume_spec_lane
engine_spec_accepted_len                histogram  tokens   serve/scheduler.py  PagedEngine._consume_spec_lane
pages_in_use                            gauge      pages    serve/paged_cache.py PageAllocator
pages_shared                            gauge      pages    serve/paged_cache.py PageAllocator
engine_kv_bytes_in_use                  gauge      bytes    serve/paged_cache.py PageAllocator
pages_alloc_total                       counter    pages    serve/paged_cache.py PageAllocator.alloc
pages_free_total                        counter    pages    serve/paged_cache.py PageAllocator.free
pages_shared_total                      counter    pages    serve/paged_cache.py PageAllocator.share
prefix_hits_total                       counter    admissions serve/prefix_cache.py PrefixCache.note_admission
prefix_misses_total                     counter    admissions serve/prefix_cache.py PrefixCache.note_admission
prefix_hit_tokens                       histogram  tokens   serve/prefix_cache.py PrefixCache.note_admission
prefix_inserted_pages_total             counter    pages    serve/prefix_cache.py PrefixCache.insert
prefix_evicted_pages_total              counter    pages    serve/prefix_cache.py PrefixCache.evict
prefix_cached_pages                     gauge      pages    serve/prefix_cache.py PrefixCache
batcher_ticks_total                     counter    ticks    serve/decode.py     ContinuousBatcher.step
batcher_dispatches_total                counter    calls    serve/decode.py     ContinuousBatcher.step
batcher_occupancy                       histogram  ratio    serve/decode.py     ContinuousBatcher.step
kernel_dispatch_total.<site>.<path>     counter    traces   kernels/ops.py      every dispatcher
kernel_dispatch_total.<site>.<kv>.<path> counter   traces   kernels/ops.py      paged dispatchers, quantized KV (<kv> = int8|fp8)
train_steps_total                       counter    steps    train/trainer.py    train()
train_tokens_total                      counter    tokens   train/trainer.py    train()
train_step_ms                           histogram  ms       train/trainer.py    train()
train_<metric>                          gauge      —        train/trainer.py    every logged step scalar
train_eval_ppl                          gauge      ppl      train/trainer.py    eval cadence
======================================  =========  =======  ==========================================

Layer-scope reference
=====================

``obs.trace.LAYER_SCOPES`` names the layers of the train step and the
packed serving tick; each is a ``jax.named_scope`` (``obs.trace.layer_scope``)
and so lives only in the compiled program's metadata: every HLO op carries
its name stack (``op_name``), and a device trace carries it per op as the
``tf_op`` stat.  A reader takes the innermost scope of the table in the
stack (``kv_write`` sits inside ``attn``); backward ops read
``transpose(jvp(...))`` around the same scopes.  XLA gives a fusion the
metadata of its root op, so a fusion is counted under its root's scope.

==========  =================================================================
scope       opened around
==========  =================================================================
embed       models/model.py  _embed_tokens (token and position embeddings)
attn        models/blocks.py  ln1 + the attention call, every phase and variant
kv_write    models/attention.py  paged_scatter / packed_scatter (K, V, scales);
            models/model.py  the paged layer scan (_decoder_layer_stack):
            its slices of each layer's page pools and weights, the pools'
            layout copies and write-back, and any op of the layer body
            that no inner scope claims (the residual adds, where XLA
            leaves them unfused)
fal_signal  models/blocks.py  fal.mlp_input / fal.block0_mlp_input
mlp         models/blocks.py  _ffn_apply
assemble    models/blocks.py  _assemble (TP all-reduce), the SP reduce-scatter
head        models/model.py  _logits, _ce_tail, lm_head_segment_tail
sample      serve/scheduler.py  make_packed_step's fused sampler
optimizer   train/step.py  adamw_update (clip, global norm, moments)
==========  =================================================================

``obs.trace.SUB_SCOPES`` name parts of one layer (``obs.trace.sub_scope``),
nested inside its scope, so a reader of the table above still counts their
ops under that layer:

==========  =====  ========================================================
sub-scope   layer  opened around
==========  =====  ========================================================
router      mlp    models/moe.py  moe_apply_held: _route (router matmul,
                   scores, top-k, gates, balance loss)
experts     mlp    models/moe.py  moe_apply_held: the held experts' sort,
                   gathers, grouped matmuls and combine
==========  =====  ========================================================

The engine's host spans (``PagedEngine``, ``annotate=True``, so they sit on
the profiler's clock beside the device ops): ``engine.tick`` around
``engine.admit`` (``_admit``), ``engine.plan`` (``_plan_pack`` and
``_ensure``, preemption and ``engine.cow`` included), ``engine.upload``
(the tick's host arrays to the device), ``engine.dispatch`` (the jitted
call), ``engine.sync`` (the wait for the sampled tokens) and
``engine.consume``.  ``engine_dispatch_ms`` times a tick's dispatch
through its sync.

``kernel_dispatch_total`` counts TRACES, not executed calls: the
dispatchers run under ``jax.jit``, so the per-site record fires when a
(site, shape) program is traced and the chosen path cannot change without
a re-trace — exactly the invariant the BENCH dispatch-path labels need.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from repro.obs.runmeta import run_metadata  # noqa: F401
from repro.obs.trace import NULL_TRACER, Tracer  # noqa: F401
