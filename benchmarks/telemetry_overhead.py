#!/usr/bin/env python
"""Measured cost of GraftTrace — the off-is-free contract, quantified.

Two numbers per state (prints one JSON line):

- ``span_ns`` — wall cost of one ``tracer().span(...)`` enter/exit,
  median over batches of 10k spans.  Off: one attribute check returning
  the shared NOOP span (no generator frame, no allocation, no I/O).  On
  (journal to a tmpfile): two JSON lines written + flushed per span, the
  price a traced run pays per unit of work.
- ``bench_site_overhead_pct`` — the off-state span cost projected onto
  the nb_mi bench's span sites per pass (a handful of spans around
  multi-second device passes), documenting why the published
  canary-clean band needs no widening with telemetry merged.

Round 14 adds ``profile_site_ns_off`` — the cost of a GraftProf sample
site (``profiler().sample``/``observe`` guard) while ``profile.on`` is
unset: one attribute check and an early return, the same off-is-free
contract the span sites hold.

Round 15 (GraftFleet) re-measures the off-state bound with the fleet
plane merged — the shard/stamp/skew/SLO machinery adds NOTHING to the
off path (``span_ns_off`` is the same one-attribute-check site; the
skew probe and SLO evaluator are gated behind the same
``profiler().enabled`` check ``profile_site_ns_off`` measures, and no
journal shard is ever created off) — and adds
``span_ns_on_federated``: the on-state cost when the journal is a
fleet SHARD (writer stamp on every event + prefixed span ids), so the
per-event price of per-process attribution is a published number.

Round 21 (GraftBox) adds the flight-ring numbers: ``ring_record_ns`` —
one bounded-deque append, the cost every emit seam now pays on BOTH
sides of ``trace.on`` — plus ``event_site_ns_off`` (a disabled
``tracer().event(...)`` call: the ring append + one enabled check, the
always-on recorder's whole off-state price) and ``event_site_ns_on``
(ring append + journal line).  ``span_ns_off`` is measured by the SAME
code as before the recorder merged — the span sites do not touch the
ring, so the published off-is-free span bound is unchanged by round 21.

PR 27 makes a span site live under a running JAX profiler session too, so
the off state now pays one static call besides the attribute check
(``jax.profiler.TraceAnnotation.is_enabled()``): ``span_ns_off`` is
measured with jax imported, as in a serving process, and
``span_ns_on_profiled`` is the cost of a span while a profiler session
runs and no journal is open — a ``TraceAnnotation`` entered and left plus
one record appended to the in-memory recorder, what a traced benchmark
run pays per span.
"""

from __future__ import annotations

import json
import os
import tempfile
import time

import jax
import numpy as np

from avenir_tpu.telemetry import blackbox
from avenir_tpu.telemetry.profile import Profiler
from avenir_tpu.telemetry.spans import Tracer

SPANS_PER_BATCH = 10_000
BATCHES = 7


def measure_span_ns(tracer: Tracer) -> float:
    rates = []
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        for _ in range(SPANS_PER_BATCH):
            with tracer.span("probe"):
                pass
        rates.append((time.perf_counter() - t0) / SPANS_PER_BATCH * 1e9)
    return float(np.median(rates))


def measure_ring_record_ns() -> float:
    """One direct flight-ring append — the GraftBox always-on floor."""
    rates = []
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        for _ in range(SPANS_PER_BATCH):
            blackbox.ring_record("probe", None)
        rates.append((time.perf_counter() - t0) / SPANS_PER_BATCH * 1e9)
    return float(np.median(rates))


def measure_event_ns(t: Tracer) -> float:
    """One ``.event()`` emit seam: off-state this is the ring append plus
    the enabled check (the recorder's whole always-on price); on-state it
    adds the journal line."""
    rates = []
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        for _ in range(SPANS_PER_BATCH):
            t.event("probe")
        rates.append((time.perf_counter() - t0) / SPANS_PER_BATCH * 1e9)
    return float(np.median(rates))


def measure_profile_site_ns(prof: Profiler) -> float:
    key = (("probe",),)
    rates = []
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        for _ in range(SPANS_PER_BATCH):
            prof.sample(key, "probe", 0.0)
        rates.append((time.perf_counter() - t0) / SPANS_PER_BATCH * 1e9)
    return float(np.median(rates))


def measure() -> dict:
    off = Tracer()                       # never enabled: the default state
    off_ns = measure_span_ns(off)
    prof_off_ns = measure_profile_site_ns(Profiler())
    ring_ns = measure_ring_record_ns()
    event_off_ns = measure_event_ns(off)

    on = Tracer()
    with tempfile.TemporaryDirectory() as tmp:
        on.enable(tmp)
        on_ns = measure_span_ns(on)
        journal_bytes = os.path.getsize(on.journal_path)
        event_on_ns = measure_event_ns(on)    # after the size read: the
        on.disable()                          # bytes/span metric is spans-only
    blackbox.ring_clear()                # drop the probe flood

    # federated shard (GraftFleet): writer stamp on every event +
    # prefixed span ids — the per-process-attribution price, on-state
    fed = Tracer()
    with tempfile.TemporaryDirectory() as tmp:
        fed.enable(tmp, run_id="bench", suffix="w0")
        fed_ns = measure_span_ns(fed)
        fed_bytes = os.path.getsize(fed.journal_path)
        fed.disable()

    # a profiler session and no journal: annotation + recorder append
    profiled = Tracer()
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            profiled_ns = measure_span_ns(profiled)
        finally:
            jax.profiler.stop_trace()

    # the nb_mi bench adds ~7 span sites per run (one bench span, five
    # pass spans, plus per-pass canary events); a pass is seconds of
    # device time, so project the off cost onto one 1-second pass
    bench_spans_per_pass = 2
    overhead_pct = off_ns * bench_spans_per_pass / 1e9 / 1.0 * 100.0
    return {
        "metric": "telemetry_overhead",
        "span_ns_off": round(off_ns, 1),
        "profile_site_ns_off": round(prof_off_ns, 1),
        "ring_record_ns": round(ring_ns, 1),
        "event_site_ns_off": round(event_off_ns, 1),
        "event_site_ns_on": round(event_on_ns, 1),
        "span_ns_on_journaled": round(on_ns, 1),
        "span_ns_on_profiled": round(profiled_ns, 1),
        "host": f"{os.cpu_count()} cores, {jax.devices()[0].platform}",
        "span_ns_on_federated": round(fed_ns, 1),
        "journal_bytes_per_span": round(journal_bytes
                                        / (SPANS_PER_BATCH * BATCHES), 1),
        "federated_bytes_per_span": round(fed_bytes
                                          / (SPANS_PER_BATCH * BATCHES), 1),
        "bench_site_overhead_pct": round(overhead_pct, 6),
        "spans_per_batch": SPANS_PER_BATCH,
        "batches": BATCHES,
    }


def main() -> None:
    print(json.dumps(measure()))


if __name__ == "__main__":
    main()
