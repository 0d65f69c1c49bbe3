"""Inference engine: compile pattern-pruned CNNs into executable programs.

The paper's deployment story made real: ``lowering`` turns pruned dense
weights into compressed spmm operands (reorder -> compress -> index; a
conv's im2col rows are tap-major where its K spans more than one block,
so the taps no kernel uses drop out as whole zero bricks),
``program`` is the compiled artifact (ops + geometry + crossbar pricing),
``executor`` runs it through the Pallas/XLA kernels (single-device or
sharded over a mesh via ``partition`` — tile-parallel spmm with psum
combine, batch-parallel service slots), ``serialize`` persists it,
``scheduler`` is the continuous-batching control plane (bounded queue,
slot refill, validity mask, latency/occupancy metrics), ``service``
serves traffic over it, and ``stats`` measures activation-skip
statistics on the served traffic so the crossbar energy pricing uses
observed (not assumed) skip probabilities.

Note: the model's BN stand-in (``channel_norm``) is per-sample, so a
request's logits never depend on which other requests share its batch.
``InferenceService`` exploits that to run every batch at the fixed
``batch_slots`` shape — dead slots zero-padded and masked out of the
statistics — so the forward traces exactly once for any traffic pattern.

Observability
-------------
The whole stack is instrumented through ``repro.obs`` — pure stdlib and
free when off (``tracer=None`` resolves to a shared no-op tracer; a
tracer never reaches the jitted forward).

* **Tracing.** Pass one ``obs.Tracer`` through the layers you care
  about: ``CompileOptions(tracer=tr)`` makes ``compile_network``
  record the lowering phases (``prune -> reorder -> pack -> quantize`` under per-layer
  ``lower:<name>`` spans), and ``InferenceService(..., tracer=tr)``
  emits per-request async lifecycles (enqueue ``b`` -> admit ``n`` ->
  done ``e``) plus queue-depth/slot-occupancy counter tracks.
  ``tr.write("trace.json")`` produces Chrome trace-event JSON — load it
  in Perfetto or chrome://tracing to see compile and serve on one
  timeline.
* **Profiles.** Under ``jax.profiler`` every span (the no-op tracer's
  too) is also a host event on the profiler's clock — each serving step
  is ``service.step`` with ``service.refill`` / ``dispatch`` / ``wait``
  / ``complete`` children — and the jitted forward's device operations
  carry ``<layer>/<stage>`` names (``make_forward``), so device time
  and idle gaps can be attributed to layers, stages and host phases.
  Per-layer seconds read from such a profile feed
  ``CompiledNetwork.hardware_report(observed=...)``, whose ``drift``
  section compares each layer's *share* of measured time against its
  share of predicted crossbar cycles.
* **Metrics.** ``SchedulerMetrics.snapshot()`` includes
  histogram-backed ``latency_p50_s``/``latency_p99_s`` and the
  queue-wait vs in-flight latency breakdown;
  ``InferenceService.metrics_text()`` renders the same registry in
  Prometheus text exposition for scraping.  Process-global metrics live
  in ``repro.obs.get_registry()`` (resettable for test isolation).

Mapping optimization
--------------------
``CompileOptions(optimize='auto')`` (or ``optimize=MappingSearchConfig(
...)``) makes ``compile_network`` run the per-layer mapping design-space search
(``core/mapsearch.py``) before lowering each conv: a seeded greedy
descent with restarts over crossbar dims x packing order
(``block_order``) x column-reorder strategy, priced by the simulator's
own cost chain (``core/simulator.mapping_cost``) so the predicted
area/energy/cycles equal ``hardware_report`` numbers exactly.  Selection
is Pareto-guarded — the chosen candidate is never worse than the fixed
paper scheme on *both* crossbar area-cells and energy, falling back to
the fixed scheme on ties — and fully deterministic for a given seed.
How it composes:

* **precision=** — the search prices the cell-slice count the program
  actually stores (int8 -> ``ceil(8 / cell_bits)`` cells/weight, fp32 ->
  the crossbar model default), so a quantized program's searched area is
  the quantized area.  Note int8 logits are only tolerance-equal across
  reorder strategies: per-brick quantization scales depend on column
  grouping.  fp32 logits agree to rounding — reordering changes layout,
  never semantics, but a layer's order also orders the sums of the
  layer that reads it (its rows are lowered in that order).
* **verify=** — searched programs pass the same static verifier;
  the candidate itself is checked by rules V205 (strategy tags) and
  V206 (geometry consistent with the packed operands).
* **partitioning / sharded execution** — the searched reorder produces
  the same ``BlockPatternWeight`` contract, so ``partition_network``
  and the mesh executor apply unchanged.
* **serialization** — the chosen ``MappingCandidate`` per conv and the
  FC reorder tag ride in the manifest and ``hardware_report`` prices
  each layer at its stored candidate after reload.
* **tracing** — each layer's search lands as a ``search:<name>``
  compile span carrying evaluations / chosen candidate / area-vs-fixed,
  next to the ``lower:<name>`` spans.

Serving
-------
The serving front door lives in ``repro.serve`` — one
:class:`~repro.serve.Request`/:class:`~repro.serve.Response` contract and
one ``submit``/``stream``/``run`` verb set over both backends:

* **Classification** — ``repro.serve.classify_session(program)`` wraps
  :class:`InferenceService` (this package): fixed-shape continuous
  batching over the jitted engine forward, traced exactly once.
* **Generation** — ``repro.serve.generate_session(cfg, statics, params,
  scfg)`` wraps ``runtime.serve.DecodeService``: per-slot decode
  positions, so freed slots are refilled *mid-decode* while other
  requests keep decoding — and every request's tokens are bit-identical
  to running it alone.
* **HTTP** — ``repro.serve.ServingServer(session)`` is a stdlib-asyncio
  HTTP/1.1 front end: ``POST /v1/run`` (one request/response),
  ``POST /v1/stream`` (chunked NDJSON in completion order),
  ``GET /healthz``, ``GET /metrics`` (Prometheus text).  All jitted
  calls run on one worker thread; the event loop only parses, enqueues,
  and resolves futures.  Over capacity it *sheds*: HTTP 429 with a
  backpressure-derived ``Retry-After``, while already-admitted work is
  never dropped (``SchedulerFull`` never escapes the public path —
  sessions translate it to ``repro.serve.Overloaded``).

``examples/serve_http.py`` boots the full stack and reports req/s,
first-result p50/p99, and slot occupancy; ``benchmarks/bench_engine.py
http_service`` gates the same numbers in CI.

Compile options
---------------
:class:`CompileOptions` is the one frozen object carrying everything
``compile_network`` accepts beyond the network itself — lowering
geometry (``block``/``tile``/``precision``/``cell_bits``) plus the
compile-pass switches (``verify``/``optimize``/``tracer``):
``compile_network(cfg, params, bits, options=CompileOptions(...))``.

Verification
------------
``repro.analysis`` statically checks compiled programs — pure numpy
over the operands, no kernel execution — and is wired in at every
trust boundary:

* ``CompileOptions(verify='strict')`` verifies the freshly built
  program and raises ``analysis.VerificationError`` listing every
  violated invariant; ``verify='warn'`` emits a single warning instead;
  the default ``None`` skips it (compile output is trusted by
  construction — turn it on when changing the lowering itself).
* ``load_program(directory)`` verifies by default: the manifest is
  validated *before* any array is constructed (a malformed or
  version-skewed save raises ``analysis.ProgramFormatError``, rule
  ``M001``–``M005``), then the loaded program is semantically verified
  (``V1xx``/``V2xx``/``V3xx`` rules).  Pass ``verify=False`` on hot
  paths that reload a program the same process just saved.
* ``partition_network`` always validates the partition geometry
  (``V4xx``: shard counts, tile disjoint-cover, distinct axes) — it is
  cheap and a bad partition fails far from its cause otherwise.
* ``CompiledNetwork.verify()`` returns the full diagnostic ``Report``
  for ad-hoc inspection; ``python -m repro.analysis verify <dir>``
  does the same for a saved program from the command line.

Each ``Diagnostic`` carries a stable rule id, severity, layer, and
location string; ``Report.format()`` renders them one per line.
Warnings (e.g. over-allocated ``k_max``, non-canonical pack order)
never raise — only errors do.  The companion trace-safety lint
(``python -m repro.analysis lint src/repro``) runs in CI and keeps
wall-clock reads, host RNG, unsynchronized timing, and unlocked
shared-state mutation out of the source tree.

Certification
-------------
Verification proves the program is *well-formed*; the range
certification pass (``repro.analysis.ranges``) proves facts about what
it can *compute*.  It is an abstract interpreter over the compiled
schedule: from a declared input interval it propagates sound activation
bounds through every layer (spmm -> channel-norm -> relu -> pool ->
head) and derives activation-independent worst-case accumulator extrema
for the quantized path.  Structural rules are ``V1xx``–``V4xx``/
``M0xx``; semantic rules are ``V5xx`` (accumulator overflow, scale
saturation/denormal, dead scale groups, range divergence, unreachable
cell slices, stale stored certificates).

When ``CompileOptions(verify=...)`` is on, the pass runs right
after verification and attaches a ``RangeCertificate`` to the program:
per-layer activation bounds plus a certified minimum cells-per-weight
table on the layer's reference scale grid.  The certificate rides in
the manifest, ``hardware_report()`` prices it as a
``certified_potential`` section (certified-vs-stored crossbar
area/energy, exactly on the simulator's own cost chain), and
``python -m repro.analysis ranges <dir>`` recomputes
and cross-checks it for a saved program (rule ``V506`` fires if the
stored certificate disagrees).  ``python -m repro.analysis all <dir>``
runs verify + lint + ranges with one merged JSON report.
"""

from repro.engine.executor import (
    execute,
    extract_patches,
    make_forward,
    warmup_forward,
)
from repro.engine.scheduler import (
    SchedulerFull,
    SchedulerMetrics,
    SlotScheduler,
)
from repro.engine.partition import (
    NetworkPartition,
    pad_bp_tiles,
    partition_from_mesh,
    partition_network,
    tile_assignment,
)
from repro.core.mapping import MappingCandidate
from repro.core.mapsearch import (
    MappingSearchConfig,
    MappingSearchResult,
    search_layer_mapping,
)
from repro.engine.lowering import (
    PRECISIONS,
    CompileOptions,
    compile_network,
    conv_mapping_search,
    lower_conv,
    lower_fc,
    lower_matrix,
)
from repro.engine.program import CompiledConv, CompiledFC, CompiledNetwork
from repro.engine.serialize import load_program, save_program
from repro.engine.service import InferenceService
from repro.engine.stats import (
    ActivationStats,
    LayerSkipStats,
    skip_patterns_and_masks,
    stats_from_counts,
)

__all__ = [
    "PRECISIONS",
    "CompileOptions",
    "compile_network",
    "conv_mapping_search",
    "lower_conv",
    "lower_fc",
    "lower_matrix",
    "MappingCandidate",
    "MappingSearchConfig",
    "MappingSearchResult",
    "search_layer_mapping",
    "CompiledConv",
    "CompiledFC",
    "CompiledNetwork",
    "make_forward",
    "warmup_forward",
    "execute",
    "extract_patches",
    "save_program",
    "load_program",
    "InferenceService",
    "SchedulerFull",
    "SchedulerMetrics",
    "SlotScheduler",
    "NetworkPartition",
    "pad_bp_tiles",
    "partition_from_mesh",
    "partition_network",
    "tile_assignment",
    "ActivationStats",
    "LayerSkipStats",
    "skip_patterns_and_masks",
    "stats_from_counts",
]
