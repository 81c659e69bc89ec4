"""FleetServe replica-pool tests.

The heart is failover CORRECTNESS: a replica killed mid-batch (through
the conf-armed ``fault.serve.dispatch`` site — no monkeypatching) has its
in-flight requests re-scored on a survivor byte-identical to the
single-replica path, a request that exhausts ``pool.failover.retries``
sheds with a typed error, and no request is ever scored twice — the
dedupe asserted from per-request ``serve.request`` journal spans (each
carries its pool ``rid``).  Around it: health-gated routing, the
per-replica breaker (trip on consecutive infra errors, half-open probe
recovery), heartbeat-deadline detection of a wedged dispatcher, the
rolling pool-wide hot-swap, the burn-rate/queue autoscaler, and the
pool-mode ``/healthz`` + ``/metrics`` + ``/stats`` surfaces.
"""

import json
import time
import urllib.request

import pytest

from avenir_tpu.core.config import JobConfig
from avenir_tpu.core.csv_io import write_csv
from avenir_tpu.datagen.churn import CHURN_SCHEMA_JSON, generate_churn
from avenir_tpu.jobs import get_job
from avenir_tpu.jobs.base import read_lines
from avenir_tpu.serving import (
    BucketedMicrobatcher,
    ModelRegistry,
    ReplicaDownError,
    ScoreHTTPServer,
    ServableModel,
    ShedError,
)
from avenir_tpu.serving.pool import CLOSED, OPEN, ReplicaPool
from avenir_tpu.telemetry import spans as tel
from avenir_tpu.telemetry.journal import read_events


# ---------------------------------------------------------------------------
# fixtures: a real NB artifact (byte-identity tests) + a fast fake family
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ws(tmp_path_factory):
    root = tmp_path_factory.mktemp("fleetserve")
    j = lambda *p: str(root.joinpath(*p))
    rows = generate_churn(400, seed=7)
    write_csv(j("train.csv"), rows[:320])
    write_csv(j("test.csv"), rows[320:])
    root.joinpath("churn.json").write_text(json.dumps(CHURN_SCHEMA_JSON))
    churn = {"feature.schema.file.path": j("churn.json")}
    get_job("BayesianDistribution").run(JobConfig(dict(churn)),
                                        j("train.csv"), j("nb_model"))
    return {"j": j, "churn": churn}


class EchoServable(ServableModel):
    """Deterministic fake: instant scoring (``<line>,<tag>``), optional
    leading failures (non-ServingError — the INFRA fault class the
    breaker counts) — the pool's control flow without model-load cost."""

    family = "echo"

    def __init__(self, tag="v1", fail_first=0):
        super().__init__()
        self.tag = tag
        self.fail_first = fail_first

    def score_lines(self, lines, pad_to):
        self.compile_keys.add((pad_to,))
        if self.fail_first > 0:
            self.fail_first -= 1
            raise RuntimeError("injected infra fault")
        return [f"{line},{self.tag}" for line in lines]

    def warmup(self, pad_to):
        self.compile_keys.add((pad_to,))


def echo_registry_factory(entries=None):
    """A per-replica registry factory; ``entries`` (a list) hands each
    successive replica its own pre-built servable (flaky r0, healthy r1)."""
    pending = list(entries) if entries else []

    def factory():
        entry = pending.pop(0) if pending else EchoServable()
        return ModelRegistry().add("echo", entry)

    return factory


def echo_pool(props, entries=None, **kwargs):
    conf = JobConfig({"serve.bucket.sizes": "1,4",
                      "serve.flush.deadline.ms": "5", **props})
    return ReplicaPool.from_conf(
        conf, registry_factory=echo_registry_factory(entries), **kwargs)


@pytest.fixture
def traced(tmp_path):
    """A journaling tracer for the duration of one test."""
    tracer = tel.tracer().enable(str(tmp_path))
    try:
        yield tracer
    finally:
        tel.tracer().disable()


def _request_spans(path):
    """rid → scored-span count from a journal (the dedupe oracle)."""
    out = {}
    for e in read_events(path):
        if e.get("ev") == "span.close" and e.get("name") == "serve.request":
            rid = (e.get("attrs") or {}).get("rid")
            if rid:
                out[rid] = out.get(rid, 0) + 1
    return out


# ---------------------------------------------------------------------------
# failover correctness (the tentpole contract)
# ---------------------------------------------------------------------------

def test_failover_rescore_byte_identical_and_never_double(ws, traced):
    """A replica killed mid-batch (conf-armed serve.dispatch site) has
    its in-flight requests re-scored on the survivor BYTE-IDENTICAL to
    the single-replica path, and the journal's per-rid spans prove no
    request was lost or scored twice."""
    j, churn = ws["j"], ws["churn"]
    lines = read_lines(j("test.csv"))[:16]
    props = {**churn, "bayesian.model.file.path": j("nb_model"),
             "serve.models": "naiveBayes", "serve.bucket.sizes": "1,2,4"}
    # the single-replica oracle
    oracle_b = BucketedMicrobatcher.from_conf(
        ModelRegistry.from_conf(JobConfig(dict(props))),
        JobConfig(dict(props)))
    try:
        oracle = [oracle_b.submit("naiveBayes", ln) for ln in lines]
    finally:
        oracle_b.close()
    pool = ReplicaPool.from_conf(JobConfig({
        **props, "pool.replicas": "2", "pool.monitor.interval.ms": "40",
        "pool.failover.retries": "1", "serve.flush.deadline.ms": "20",
        "fault.serve.dispatch.crash.after": "2"}))
    try:
        reqs = [pool.submit_nowait("naiveBayes", ln) for ln in lines]
        served = [r.wait(60.0) for r in reqs]
        assert served == oracle
        stats = pool.stats()["pool"]
        assert stats["replicas.lost"] == 1
        assert stats["failovers"] >= 1
        time.sleep(0.2)                   # let the monitor journal the loss
    finally:
        pool.close()
    spans = _request_spans(traced.journal_path)
    assert spans, "serve.request spans carry no rid"
    assert all(n == 1 for n in spans.values()), f"double-scored: {spans}"
    assert set(spans) == {r.rid for r in reqs}        # zero lost
    events = read_events(traced.journal_path)
    downs = [e for e in events if e["ev"] == "pool.replica.down"]
    assert any(e["reason"] == "died" for e in downs)
    assert any(e["ev"] == "fault.injected" and e["site"] == "serve.dispatch"
               for e in events)
    assert any(e["ev"] == "pool.failover" for e in events)


def test_failover_exhausted_sheds_typed(ws):
    """pool.failover.retries=0: a killed replica's requests shed with a
    typed ShedError (never silent loss), while the survivor's requests
    still score — and the counters book every shed."""
    j, churn = ws["j"], ws["churn"]
    lines = read_lines(j("test.csv"))[:12]
    pool = ReplicaPool.from_conf(JobConfig({
        **churn, "bayesian.model.file.path": j("nb_model"),
        "serve.models": "naiveBayes", "serve.bucket.sizes": "1,2,4",
        "serve.flush.deadline.ms": "20",
        "pool.replicas": "2", "pool.monitor.interval.ms": "40",
        "pool.failover.retries": "0",
        "fault.serve.dispatch.crash.after": "2"}))
    try:
        reqs = [pool.submit_nowait("naiveBayes", ln) for ln in lines]
        ok = shed = 0
        for r in reqs:
            try:
                r.wait(60.0)
                ok += 1
            except ShedError:
                shed += 1
        assert ok + shed == len(lines)    # every request has ONE outcome
        assert shed >= 1 and ok >= 1
        assert pool.counters.get("Pool", "failover.exhausted") == shed
        assert pool.counters.get("Serving.naiveBayes", "shed") >= shed
    finally:
        pool.close()


def test_no_ready_replicas_sheds_at_the_door():
    pool = echo_pool({"pool.replicas": "1"})
    try:
        with pool._lock:
            replica = next(iter(pool._replicas.values()))
        replica.breaker = OPEN            # health gate: nothing routable
        with pytest.raises(ShedError):
            pool.submit_nowait("echo", "row")
        assert pool.counters.get("Pool", "no.ready") == 1
        assert not pool.ready
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# breaker: trip on consecutive infra errors, half-open probe recovery
# ---------------------------------------------------------------------------

def test_breaker_trips_and_probe_recovers():
    flaky = EchoServable(fail_first=2)
    pool = echo_pool({"pool.replicas": "1",
                      "pool.breaker.failures": "2",
                      "pool.breaker.halfopen.ms": "60",
                      "pool.monitor.interval.ms": "30"},
                     entries=[flaky])
    try:
        # two consecutive infra-failed dispatches -> breaker opens
        for _ in range(2):
            with pytest.raises(Exception):
                pool.submit("echo", "row", timeout_s=10.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and pool.ready:
            time.sleep(0.02)
        assert not pool.ready             # open breaker gates routing
        assert pool.counters.get("Pool", "breaker.trips") == 1
        with pytest.raises(ShedError):
            pool.submit_nowait("echo", "row")
        # half-open: the monitor's probe rides the real dispatch queue;
        # the fake is healthy again, so the breaker closes and traffic
        # resumes on the SAME replica
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not pool.ready:
            time.sleep(0.02)
        assert pool.ready
        assert pool.submit("echo", "row9", timeout_s=10.0) == "row9,v1"
        assert pool.counters.get("Pool", "breaker.closes") == 1
    finally:
        pool.close()


def test_bad_requests_do_not_trip_the_breaker(ws):
    """Typed request faults (bad rows) are the CLIENT's problem — only
    infrastructure errors count toward the breaker, so a bad-request
    storm can never take a healthy replica out of rotation."""
    j, churn = ws["j"], ws["churn"]
    pool = ReplicaPool.from_conf(JobConfig({
        **churn, "bayesian.model.file.path": j("nb_model"),
        "serve.models": "naiveBayes", "serve.bucket.sizes": "1",
        "pool.replicas": "1", "pool.breaker.failures": "2"}))
    try:
        from avenir_tpu.serving import RequestError

        for _ in range(4):
            with pytest.raises(RequestError):
                pool.submit("naiveBayes", "too,few", timeout_s=30.0)
        assert pool.ready
        assert pool.counters.get("Pool", "breaker.trips") == 0
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# heartbeat: a wedged dispatcher is detected and its queue failed over
# ---------------------------------------------------------------------------

def test_wedged_dispatcher_detected_by_heartbeat_deadline(traced):
    """fault.serve.heartbeat wedges one dispatcher mid-soak (the thread
    exits WITHOUT finishing pending work): the pool's deadline detection
    reaps the stranded queue, requests fail over, every submission still
    completes, and the journal explains the loss."""
    # the deadline must outlast a HEALTHY dispatcher's worst scheduling
    # delay: with 150 ms, six xdist workers beside the chip-compile cases
    # (PR 23) starved the survivor past it and the pool shed at the door
    pool = echo_pool({"pool.replicas": "2",
                      "pool.heartbeat.ms": "600",
                      "pool.monitor.interval.ms": "40",
                      "fault.serve.heartbeat.crash.after": "3"})
    try:
        reqs = []
        for i in range(30):
            reqs.append(pool.submit_nowait("echo", f"row{i}"))
            time.sleep(0.015)
        outs = [r.wait(30.0) for r in reqs]
        assert outs == [f"row{i},v1" for i in range(30)]
    finally:
        pool.close()
    events = read_events(traced.journal_path)
    downs = [e for e in events if e["ev"] == "pool.replica.down"]
    assert any(e["reason"] == "heartbeat" for e in downs), downs
    assert any(e["ev"] == "fault.injected" and e["site"] == "serve.heartbeat"
               for e in events)
    spans = _request_spans(traced.journal_path)
    assert all(n == 1 for n in spans.values())


# ---------------------------------------------------------------------------
# rolling hot-swap: capacity never zero, every live replica advances
# ---------------------------------------------------------------------------

def test_rolling_swap_advances_every_replica():
    pool = echo_pool({"pool.replicas": "2"})
    try:
        assert pool.submit("echo", "a", timeout_s=10.0) == "a,v1"
        versions = pool.swap("echo", EchoServable(tag="v2"))
        assert versions == {"r0": 2, "r1": 2}
        assert pool.submit("echo", "b", timeout_s=10.0) == "b,v2"
        health = pool.health()
        assert health["versions"] == {"echo": 2}
        assert all(row["versions"] == {"echo": 2}
                   for row in health["replicas"])
        # zero steady-state recompiles across the rollout (the warmup
        # barrier ran per replica)
        assert pool.counters.get("Serving.echo", "recompiles") == 0
    finally:
        pool.close()


def test_replica_spawned_after_swap_serves_swapped_version():
    """A replica spawned AFTER a rolling swap (autoscale growth or
    replacement) must come up on the swapped entry, not re-load the
    conf's original artifact — else it would silently serve stale
    predictions from inside a green pool."""
    pool = echo_pool({"pool.replicas": "1"}, start_monitor=False)
    try:
        pool.swap("echo", EchoServable(tag="v2"))
        newcomer = pool._spawn(reason="test")     # the growth path
        assert newcomer.batcher.registry.version("echo") == 2
        assert newcomer.batcher.submit("echo", "z", timeout_s=10.0) \
            == "z,v2"
    finally:
        pool.close()


def test_swap_skips_dead_replicas(ws):
    j, churn = ws["j"], ws["churn"]
    lines = read_lines(j("test.csv"))[:8]
    pool = ReplicaPool.from_conf(JobConfig({
        **churn, "bayesian.model.file.path": j("nb_model"),
        "serve.models": "naiveBayes", "serve.bucket.sizes": "1,2,4",
        "serve.flush.deadline.ms": "20",
        "pool.replicas": "2", "pool.monitor.interval.ms": "40",
        "fault.serve.dispatch.crash.after": "1"}))
    try:
        reqs = [pool.submit_nowait("naiveBayes", ln) for ln in lines]
        [r.wait(60.0) for r in reqs]
        time.sleep(0.2)
        from avenir_tpu.serving.registry import NaiveBayesServable

        entry = NaiveBayesServable.from_conf(JobConfig(
            {**churn, "bayesian.model.file.path": j("nb_model")}))
        versions = pool.swap("naiveBayes", entry)
        assert len(versions) == 1         # only the survivor rolled
        assert set(versions.values()) == {2}
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# autoscaler: queue pressure grows the pool, lost capacity is replaced
# ---------------------------------------------------------------------------

def test_autoscaler_grows_on_queue_pressure(traced):
    pool = echo_pool({"serve.bucket.sizes": "64",
                      "serve.flush.deadline.ms": "3000",
                      "serve.queue.depth": "8",
                      "pool.replicas": "1",
                      "pool.monitor.interval.ms": "30",
                      "pool.autoscale.on": "true",
                      "pool.autoscale.min": "1",
                      "pool.autoscale.max": "3",
                      "pool.autoscale.queue.frac": "0.3",
                      "pool.autoscale.interval.sec": "0.05"})
    try:
        reqs = [pool.submit_nowait("echo", f"row{i}") for i in range(6)]
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                pool.stats()["pool"]["replicas"] < 2:
            time.sleep(0.03)
        assert pool.stats()["pool"]["replicas"] >= 2
    finally:
        pool.close()                      # drains the held queue
    [r.wait(10.0) for r in reqs]
    events = read_events(traced.journal_path)
    scales = [e for e in events if e["ev"] == "pool.scale"]
    assert any(e["direction"] == "up" and e["reason"] == "queue"
               for e in scales)
    assert any(e["ev"] == "pool.replica.up" for e in events)


def test_autoscaler_replaces_lost_capacity(traced):
    """A killed replica is REPLACED (pool.autoscale.min), so a death
    costs shed requests at worst, never standing capacity loss."""
    pool = echo_pool({"pool.replicas": "2",
                      "pool.monitor.interval.ms": "30",
                      "pool.autoscale.on": "true",
                      "pool.autoscale.min": "2",
                      "pool.autoscale.interval.sec": "0.05",
                      "fault.serve.dispatch.crash.after": "1"})
    try:
        reqs = [pool.submit_nowait("echo", f"row{i}") for i in range(8)]
        [r.wait(30.0) for r in reqs]
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                pool.stats()["pool"]["ready"] < 2:
            time.sleep(0.03)
        assert pool.stats()["pool"]["ready"] == 2
    finally:
        pool.close()
    events = read_events(traced.journal_path)
    assert any(e["ev"] == "pool.scale" and e["reason"] == "replace"
               for e in events)
    assert any(e["ev"] == "pool.replica.up" and e["reason"] == "replace"
               for e in events)


def test_autoscaler_shrinks_when_cold():
    pool = echo_pool({"pool.replicas": "3",
                      "pool.autoscale.on": "true",
                      "pool.autoscale.min": "1",
                      "pool.autoscale.down.burn": "0.5"},
                     start_monitor=False)
    try:
        pool.autoscale_once()             # cold: no queue, no burn
        assert pool.stats()["pool"]["replicas"] == 2
        assert pool.counters.get("Pool", "scale.down") == 1
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# pool-mode /healthz, /metrics, /stats and error attribution
# ---------------------------------------------------------------------------

def test_healthz_pool_mode_rows_and_aggregate():
    pool = echo_pool({"pool.replicas": "2"})
    try:
        with ScoreHTTPServer(pool) as srv:
            host, port = srv.address
            base = f"http://{host}:{port}"
            with urllib.request.urlopen(f"{base}/healthz") as resp:
                body = json.loads(resp.read())
            assert resp.status == 200 and body["ready"]
            rows = {r["replica"]: r for r in body["replicas"]}
            assert set(rows) == {"r0", "r1"}
            assert all(r["ready"] and r["breaker"] == CLOSED
                       for r in rows.values())
            assert all(r["versions"] == {"echo": 1} for r in rows.values())
            # trip one breaker: its row goes red, the aggregate stays
            # green (>= 1 ready replica) — visible from one curl
            with pool._lock:
                pool._replicas["r1"].breaker = OPEN
            with urllib.request.urlopen(f"{base}/healthz") as resp:
                body = json.loads(resp.read())
            rows = {r["replica"]: r for r in body["replicas"]}
            assert body["ready"] and not rows["r1"]["ready"]
            assert rows["r1"]["breaker"] == OPEN
            # both down -> aggregate 503
            with pool._lock:
                pool._replicas["r0"].breaker = OPEN
            try:
                urllib.request.urlopen(f"{base}/healthz")
                assert False, "expected 503"
            except urllib.error.HTTPError as e:
                assert e.code == 503
            with pool._lock:
                pool._replicas["r0"].breaker = CLOSED
                pool._replicas["r1"].breaker = CLOSED
            # /metrics carries the pool gauges; /stats the pool row
            with urllib.request.urlopen(f"{base}/metrics") as resp:
                page = resp.read().decode()
            assert 'name="pool.replicas.ready"' in page
            assert 'name="pool.queue.r0"' in page
            with urllib.request.urlopen(f"{base}/stats") as resp:
                stats = json.loads(resp.read())
            assert stats["pool"]["replicas"] == 2
    finally:
        pool.close()


def test_shed_and_timeout_carry_replica_attribution(ws):
    j, churn = ws["j"], ws["churn"]
    props = {**churn, "bayesian.model.file.path": j("nb_model"),
             "serve.models": "naiveBayes"}
    b = BucketedMicrobatcher.from_conf(
        ModelRegistry.from_conf(JobConfig(dict(props))),
        JobConfig({**props, "serve.bucket.sizes": "64",
                   "serve.flush.deadline.ms": "5000",
                   "serve.queue.depth": "2"}), name="r7")
    try:
        line = read_lines(j("test.csv"))[0]
        held = [b.submit_nowait("naiveBayes", line) for _ in range(2)]
        with pytest.raises(ShedError) as exc:
            b.submit_nowait("naiveBayes", line)
        assert exc.value.replica == "r7"
        assert "r7" in str(exc.value)
        assert exc.value.queue_wait_ms == 0.0
    finally:
        b.close()
    assert all(h.wait(5.0) for h in held)
    bt = BucketedMicrobatcher.from_conf(
        ModelRegistry.from_conf(JobConfig(dict(props))),
        JobConfig({**props, "serve.bucket.sizes": "8",
                   "serve.flush.deadline.ms": "30",
                   "serve.request.timeout.ms": "1"}), name="r8")
    try:
        from avenir_tpu.serving import RequestTimeout

        req = bt.submit_nowait("naiveBayes", line)
        time.sleep(0.05)
        with pytest.raises(RequestTimeout) as exc:
            req.wait(30.0)
        assert exc.value.replica == "r8"
        assert exc.value.queue_wait_ms > 0
    finally:
        bt.close()


def test_single_batcher_killed_through_conf_fails_typed(ws):
    """The serve.dispatch site works on a bare batcher too (no pool):
    the replica dies mid-batch and every pending request fails with the
    typed retryable error — conf-armed, no monkeypatching."""
    j, churn = ws["j"], ws["churn"]
    b = BucketedMicrobatcher.from_conf(
        ModelRegistry.from_conf(JobConfig({
            **churn, "bayesian.model.file.path": j("nb_model"),
            "serve.models": "naiveBayes"})),
        JobConfig({**churn, "bayesian.model.file.path": j("nb_model"),
                   "serve.models": "naiveBayes",
                   "serve.bucket.sizes": "1,4",
                   "fault.serve.dispatch.crash.after": "1"}))
    try:
        line = read_lines(j("test.csv"))[0]
        reqs = [b.submit_nowait("naiveBayes", line) for _ in range(3)]
        for r in reqs:
            with pytest.raises(ReplicaDownError):
                r.wait(30.0)
        assert b.failed
        with pytest.raises(ReplicaDownError):   # refused at the door now
            b.submit_nowait("naiveBayes", line)
    finally:
        b.close()
