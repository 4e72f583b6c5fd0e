"""repro.obs — unified observability: metrics registry + span tracing.

Every measurement in the system flows through this package: the serve
engine's dispatch/traffic counters and TTFT histograms, the trainer's
loss/throughput gauges and step spans, the tile-table build's span, JAX's
compile events, and the benchmark SLO rows (benchmarks/serve_bench.py
reads engine histograms instead of recomputing percentiles). It imports
nothing outside the standard library at import time (jax is imported on
first use, and its absence turns the jax hooks off), so obs can never be
the reason a path fails to import.

Instrument taxonomy (``repro.obs.metrics``)
-------------------------------------------
* **Counter** — monotone totals. Naming: ``<subsystem>.<noun>`` with
  labels for variants (``serve.dispatches{phase=prefill|decode}``,
  ``serve.prefill.tokens{kind=total|prefilled|shared}``). Counters are
  the currency of *how much work happened*.
* **Gauge** — last-written point-in-time values: *what is the system
  doing right now* (``train.loss``, ``train.tokens_per_sec``,
  ``serve.sched.queue_depth``).
* **Histogram** — fixed-bucket latency/size distributions: *how is work
  distributed* (``serve.ttft_ticks``, ``serve.ttft_wall_ms``,
  ``train.step_ms``). No sample retention — p50/p99 come from bucket
  counts, exact for integer tick data on unit buckets.

The tick-vs-wall-clock contract
-------------------------------
The serving stack keeps TWO clocks, deliberately:

* **ticks** — the engine's dispatch clock (1 tick = 1 jit dispatch,
  prefill or decode). Ticks are DETERMINISTIC: the same workload yields
  the same tick TTFTs on any machine, so ticks are the testing and
  regression currency (``serve.ttft_ticks``, ``Request.arrival/
  t_first/t_done``, the SLO harness gates).
* **wall** — the monotonic host clock (``time.perf_counter``). Wall time
  is what an SLO actually promises a user, and the only clock that can
  see compile time, host scheduling, and real hardware speed
  (``serve.ttft_wall_ms``, ``Request.wall_arrival/wall_first/
  wall_done``).

Every latency is recorded in BOTH units; anything asserted in CI asserts
ticks, anything reported to a human shows both. Traces carry both too:
wall spans for engine/trainer phases, tick-timeline spans (1 tick =
``trace.TICK_US`` us) for per-request lifecycles — so a request's span
geometry in Perfetto reproduces its tick TTFT exactly.

Spans and the process recorder (``repro.obs.trace``)
----------------------------------------------------
``get_trace()`` is the process recorder, as ``get_registry()`` is the
process registry. It is ON by default and keeps the newest
``trace.CAPACITY`` events. The trainer records into it unless given a
``trace=`` (``train.step`` with its children ``train.data``,
``train.dispatch``, ``train.sync``, ``train.readback``), and
``kernels.ops.prepare_tile_consts`` records ``sl.tile_tables``. Its first
use installs one ``jax.monitoring`` listener for the process: every jit
trace, lowering and backend compile (or persistent-cache load) becomes a
``jax.trace`` / ``jax.lower`` / ``jax.compile`` event with its
``fun_name``, counted as ``jax.compiles{kind=trace|lower|compile|
cache_hit}`` on ``get_registry()``. Spans carry their parent's id. Each
wall span also opens a ``jax.profiler.TraceAnnotation`` of its name, so
under a profiler session the program's phases lie on the device trace's
clock.

On/off contract: recording is on unless a recorder is built with
``Trace(enabled=False)`` (or ``get_trace().enabled`` is set False); a
disabled recorder's spans are a shared no-op. With recording on and no
profiler session, a span costs a few microseconds of host time; the
annotations record only while a ``jax.profiler`` session runs.

Entry points: ``metrics.Registry`` / ``metrics.get_registry()`` and
``trace.Trace`` / ``trace.get_trace()``; JSONL sink via
``Registry.write_jsonl``; Chrome-trace export via ``Trace.export``
(validated by ``trace.validate``); optional ``jax.profiler`` sessions via
``Trace(jax_profile_dir=...)``.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricView,  # noqa: F401
                               Registry, get_registry, ms_buckets,
                               tick_buckets)
from repro.obs.trace import (TICK_US, Trace, get_trace, validate,  # noqa: F401
                             validate_file)
