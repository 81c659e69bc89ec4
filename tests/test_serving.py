"""ServeGraft scoring-plane tests.

The heart is batch-vs-serving parity: for every model family, the serving
path's responses must be BYTE-IDENTICAL to the corresponding batch
predictor's output on the same rows — the registry routes scoring through
the same model-layer entries the jobs use, and these tests pin that
contract (including kernel-weighted kNN and Viterbi state sequences).
Around it: bucketing/padding semantics, warmup vs recompiles, typed
shed/timeout/bad-request errors, both front ends, the driver `serve`
stage, and the shared RL-loop metrics schema.
"""

import json
import os
import queue
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from avenir_tpu.core.config import ConfigError, JobConfig
from avenir_tpu.core.csv_io import write_csv
from avenir_tpu.datagen.churn import CHURN_SCHEMA_JSON, generate_churn
from avenir_tpu.datagen.retarget import RETARGET_SCHEMA_JSON, generate_retarget
from avenir_tpu.jobs import get_job
from avenir_tpu.jobs.base import read_lines
from avenir_tpu.serving import (
    BucketedMicrobatcher,
    ModelRegistry,
    QueueScoreFrontend,
    RequestError,
    ReplicaDownError,
    RequestTimeout,
    ScoreHTTPServer,
    ServableModel,
    ShedError,
    UnknownModelError,
)
from avenir_tpu.serving.batcher import PendingRequest, _Batch, _Flight
from avenir_tpu.telemetry import blackbox
from avenir_tpu.telemetry import spans as tel
from avenir_tpu.telemetry.journal import read_events
from avenir_tpu.utils.metrics import LatencyTracker
from avenir_tpu.utils.retry import FaultPlan


# ---------------------------------------------------------------------------
# trained artifacts (once per module, through the real jobs)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ws(tmp_path_factory):
    root = tmp_path_factory.mktemp("servegraft")
    j = lambda *p: str(root.joinpath(*p))
    rows = generate_churn(600, seed=7)
    write_csv(j("train.csv"), rows[:480])
    write_csv(j("test.csv"), rows[480:])
    root.joinpath("churn.json").write_text(json.dumps(CHURN_SCHEMA_JSON))
    churn = {"feature.schema.file.path": j("churn.json")}
    get_job("BayesianDistribution").run(JobConfig(dict(churn)),
                                        j("train.csv"), j("nb_model"))
    get_job("LogisticRegressionJob").run(
        JobConfig({**churn, "coeff.file.path": j("coeff.txt"),
                   "iteration.limit": "8"}),
        j("train.csv"), j("lr_out"))
    rrows = generate_retarget(1000, seed=3)
    write_csv(j("rdata.csv"), rrows)
    root.joinpath("retarget.json").write_text(json.dumps(RETARGET_SCHEMA_JSON))
    retarget = {"feature.schema.file.path": j("retarget.json")}
    get_job("DecisionTreeBuilder").run(JobConfig(dict(retarget)),
                                       j("rdata.csv"), j("tree_model"))
    tagged = root.joinpath("tagged")
    tagged.mkdir()
    tagged.joinpath("part-00000").write_text(
        "c1,x:A,y:B,x:A\nc2,y:B,y:B\nc3,x:A,y:B,x:A,x:A\n")
    get_job("HiddenMarkovModelBuilder").run(JobConfig({}), str(tagged),
                                            j("hmm_model"))
    return {"j": j, "churn": churn, "retarget": retarget}


def _batcher(conf_props, **kwargs):
    conf = JobConfig(dict(conf_props))
    registry = ModelRegistry.from_conf(conf)
    return BucketedMicrobatcher.from_conf(registry, conf), conf, registry


def _serve_all(batcher, model, lines, burst=5):
    """Submit in bursts (so requests coalesce into buckets) and return the
    responses in request order."""
    out = []
    for i in range(0, len(lines), burst):
        pend = [batcher.submit_nowait(model, ln)
                for ln in lines[i:i + burst]]
        out.extend(p.wait(60.0) for p in pend)
    return out


# ---------------------------------------------------------------------------
# batch-vs-serving parity, one test per family
# ---------------------------------------------------------------------------

def test_naive_bayes_parity(ws):
    j, churn = ws["j"], ws["churn"]
    conf2 = JobConfig({**churn, "bayesian.model.file.path": j("nb_model")})
    get_job("BayesianPredictor").run(conf2, j("test.csv"), j("nb_pred"))
    batch = read_lines(j("nb_pred"))
    b, _, _ = _batcher({**churn, "bayesian.model.file.path": j("nb_model"),
                        "serve.models": "naiveBayes",
                        "serve.bucket.sizes": "1,4,16"})
    try:
        served = _serve_all(b, "naiveBayes", read_lines(j("test.csv")))
        assert served == batch
        assert b.counters.get("Serving.naiveBayes", "recompiles") == 0
    finally:
        b.close()


def test_knn_parity_with_kernel_weighting(ws):
    j, churn = ws["j"], ws["churn"]
    props = {**churn, "training.data.path": j("train.csv"),
             "top.match.count": "7", "kernel.function": "gaussian",
             "kernel.param": "0.25", "inverse.distance.weighted": "true"}
    get_job("NearestNeighbor").run(JobConfig(dict(props)), j("test.csv"),
                                   j("knn_pred"))
    batch = read_lines(j("knn_pred"))
    b, _, _ = _batcher({**props, "serve.models": "knn",
                        "serve.bucket.sizes": "1,4"})
    try:
        served = _serve_all(b, "knn", read_lines(j("test.csv"))[:60],
                            burst=4)
        assert served == batch[:60]
    finally:
        b.close()


def test_tree_parity(ws):
    j, retarget = ws["j"], ws["retarget"]
    conf2 = JobConfig({**retarget, "tree.model.file.path": j("tree_model")})
    get_job("DecisionTreeBuilder").run(conf2, j("rdata.csv"), j("tree_pred"))
    batch = read_lines(j("tree_pred"))
    b, _, _ = _batcher({**retarget, "tree.model.file.path": j("tree_model"),
                        "serve.models": "tree", "serve.bucket.sizes": "1,8"})
    try:
        served = _serve_all(b, "tree", read_lines(j("rdata.csv"))[:80],
                            burst=7)
        assert served == batch[:80]
    finally:
        b.close()


def test_tree_hot_swap_same_bucket_zero_recompiles(ws):
    """TreeGraft serving contract: predict_fn pads tree arrays to pow-2
    depth/node/segment buckets and the walker keys on SHAPES, so a
    drift→retrain→hot-swap onto a tree of a different depth (same depth
    bucket) reuses the compiled scoring program — zero recompiles counted
    by the existing CompileKeyMonitor even with the swap barrier's warmup
    DISABLED, and the module-level walker's jit cache does not grow."""
    from avenir_tpu.core.csv_io import write_csv as _write_csv
    from avenir_tpu.models import tree as dtree
    from avenir_tpu.serving.registry import TreeServable

    j, retarget = ws["j"], ws["retarget"]
    # retrained artifact: different data, depth 3 (buckets with depth 4)
    _write_csv(j("rdata2.csv"), generate_retarget(900, seed=17))
    get_job("DecisionTreeBuilder").run(
        JobConfig({**retarget, "max.depth": "3"}),
        j("rdata2.csv"), j("tree_model_v2"))
    b, _, registry = _batcher({**retarget,
                               "tree.model.file.path": j("tree_model"),
                               "serve.models": "tree",
                               "serve.bucket.sizes": "1,8"})
    try:
        lines = read_lines(j("rdata.csv"))[:16]
        _serve_all(b, "tree", lines, burst=4)
        entry_v2 = TreeServable.from_conf(JobConfig(
            {**retarget, "tree.model.file.path": j("tree_model_v2")}))
        assert entry_v2._shape_sig == registry.get("tree")._shape_sig
        cache = (dtree._tree_walk._cache_size()
                 if hasattr(dtree._tree_walk, "_cache_size") else None)
        # warm=False: the barrier would hide a recompile by paying it on
        # the caller thread — with shape-stable buckets there is nothing
        # to pay, which is exactly what the monitor now proves
        assert b.swap("tree", entry_v2, warm=False) == 2
        served = _serve_all(b, "tree", lines, burst=4)
        assert b.counters.get("Serving.tree", "recompiles") == 0
        assert b.counters.get("Serving.tree", "swaps") == 1
        if cache is not None:
            assert dtree._tree_walk._cache_size() == cache, \
                "hot-swap compiled a fresh walker despite equal buckets"
        # post-swap responses come from the NEW model (parity with its
        # own batch predictor)
        conf2 = JobConfig({**retarget,
                           "tree.model.file.path": j("tree_model_v2")})
        get_job("DecisionTreeBuilder").run(conf2, j("rdata.csv"),
                                           j("tree_pred_v2"))
        assert served == read_lines(j("tree_pred_v2"))[:16]
    finally:
        b.close()


def test_viterbi_parity_state_sequences(ws):
    j = ws["j"]
    seq_lines = ["u1,1,x,y,x", "u2,2,y", "u3,3,x,y,x,x,y", "u4,4,y,x",
                 "u5,5,x", "u6,6,y,y,x,y"]
    obs = os.path.dirname(j("obs", "part-00000"))
    os.makedirs(obs, exist_ok=True)
    with open(j("obs", "part-00000"), "w") as fh:
        fh.write("\n".join(seq_lines) + "\n")
    props = {"hmm.model.file.path": j("hmm_model"), "skip.field.count": "2"}
    get_job("ViterbiStatePredictor").run(JobConfig(dict(props)), obs,
                                         j("vit_pred"))
    batch = read_lines(j("vit_pred"))
    # serving pads every sequence to serve.sequence.pad.len, the batch job
    # to the batch max — identical paths prove pad steps are identities
    b, _, _ = _batcher({**props, "serve.models": "viterbi",
                        "serve.bucket.sizes": "1,4",
                        "serve.sequence.pad.len": "12"})
    try:
        served = _serve_all(b, "viterbi", seq_lines, burst=4)
        assert served == batch
    finally:
        b.close()


def test_logistic_parity(ws):
    from avenir_tpu.jobs.base import Job
    from avenir_tpu.models import logistic as mlr

    j, churn = ws["j"], ws["churn"]
    props = {**churn, "coeff.file.path": j("coeff.txt")}
    conf = JobConfig(dict(props))
    enc, ds, _ = Job.encode_input(conf, j("test.csv"), with_labels=False,
                                  need_rows=False)
    model = mlr.LogisticRegressionModel.from_history_lines(
        read_lines(j("coeff.txt")))
    probs, pred = mlr.predict_batch(model, mlr.design_matrix(ds))
    lines = read_lines(j("test.csv"))
    oracle = [f"{ln},{int(pred[i])},{probs[i]:.6f}"
              for i, ln in enumerate(lines)]
    b, _, _ = _batcher({**props, "serve.models": "logistic",
                        "serve.bucket.sizes": "1,4,16"})
    try:
        assert _serve_all(b, "logistic", lines) == oracle
    finally:
        b.close()


# ---------------------------------------------------------------------------
# bucketing, padding, warmup, recompiles
# ---------------------------------------------------------------------------

def test_pad_rows_never_leak_and_histogram(ws):
    """3 requests into a bucket-8 batch must score exactly like 3 lone
    bucket-1 requests — pad rows influence nothing — and the size
    histogram must show one bucket-8 batch."""
    j, churn = ws["j"], ws["churn"]
    lines = read_lines(j("test.csv"))[:3]
    props = {**churn, "bayesian.model.file.path": j("nb_model"),
             "serve.models": "naiveBayes"}
    b1, _, _ = _batcher({**props, "serve.bucket.sizes": "1"})
    try:
        singles = [b1.submit("naiveBayes", ln) for ln in lines]
    finally:
        b1.close()
    b8, _, _ = _batcher({**props, "serve.bucket.sizes": "8",
                         "serve.flush.deadline.ms": "150"})
    try:
        pend = [b8.submit_nowait("naiveBayes", ln) for ln in lines]
        batched = [p.wait(30.0) for p in pend]
        assert batched == singles
        assert b8.counters.get("Serving.naiveBayes", "bucket.8") == 1
        assert b8.counters.get("Serving.naiveBayes", "batches") == 1
    finally:
        b8.close()


def test_warmup_pins_compile_cache(ws):
    """With warmup, steady state records zero recompiles; without it, the
    first batch of each shape is counted — the invariant is measured."""
    j, churn = ws["j"], ws["churn"]
    props = {**churn, "bayesian.model.file.path": j("nb_model"),
             "serve.models": "naiveBayes", "serve.bucket.sizes": "1,2"}
    lines = read_lines(j("test.csv"))[:6]
    warm, _, _ = _batcher(props)
    try:
        _serve_all(warm, "naiveBayes", lines, burst=2)
        assert warm.counters.get("Serving.naiveBayes", "recompiles") == 0
    finally:
        warm.close()
    cold, _, _ = _batcher({**props, "serve.warmup.on.start": "false"})
    try:
        _serve_all(cold, "naiveBayes", lines, burst=2)
        assert cold.counters.get("Serving.naiveBayes", "recompiles") >= 1
    finally:
        cold.close()


def test_shed_and_timeout_and_unknown_model(ws):
    j, churn = ws["j"], ws["churn"]
    props = {**churn, "bayesian.model.file.path": j("nb_model"),
             "serve.models": "naiveBayes"}
    line = read_lines(j("test.csv"))[0]
    # shed: tiny queue, huge bucket + deadline so nothing drains
    b, _, _ = _batcher({**props, "serve.bucket.sizes": "64",
                        "serve.flush.deadline.ms": "5000",
                        "serve.queue.depth": "3"})
    try:
        held = [b.submit_nowait("naiveBayes", line) for _ in range(3)]
        with pytest.raises(ShedError):
            b.submit_nowait("naiveBayes", line)
        assert b.counters.get("Serving.naiveBayes", "shed") == 1
        with pytest.raises(UnknownModelError):
            b.submit_nowait("noSuchModel", line)
    finally:
        b.close()            # flushes the held requests
    assert all(h.wait(1.0) for h in held)
    # timeout: the request aged past the (zero) budget before dispatch
    bt, _, _ = _batcher({**props, "serve.bucket.sizes": "8",
                         "serve.flush.deadline.ms": "30",
                         "serve.request.timeout.ms": "1"})
    try:
        import time

        req = bt.submit_nowait("naiveBayes", line)
        time.sleep(0.05)
        with pytest.raises(RequestTimeout):
            req.wait(30.0)
        assert bt.counters.get("Serving.naiveBayes", "timeouts") == 1
    finally:
        bt.close()


def test_bad_request_rows_fail_typed(ws):
    j, churn = ws["j"], ws["churn"]
    b, _, _ = _batcher({**churn, "bayesian.model.file.path": j("nb_model"),
                        "serve.models": "naiveBayes",
                        "serve.bucket.sizes": "1"})
    try:
        with pytest.raises(RequestError):
            b.submit("naiveBayes", "too,few")
    finally:
        b.close()
    vb, _, _ = _batcher({"hmm.model.file.path": j("hmm_model"),
                         "skip.field.count": "2",
                         "serve.models": "viterbi",
                         "serve.bucket.sizes": "1",
                         "serve.sequence.pad.len": "4"})
    try:
        with pytest.raises(RequestError):        # unknown symbol
            vb.submit("viterbi", "u1,1,x,zzz")
        with pytest.raises(RequestError):        # longer than the pad len
            vb.submit("viterbi", "u1,1,x,y,x,y,x")
    finally:
        vb.close()


def test_bad_request_does_not_poison_batch_neighbors(ws):
    """A malformed row coalesced into the same bucket as valid concurrent
    requests must fail alone: the batcher isolates a failed batch and
    re-scores each member, so the valid rows still succeed.  In the kNN
    bucket the native encoder refuses the ragged block first and the
    Python path raises the typed error the batcher isolates on."""
    j, churn = ws["j"], ws["churn"]
    good = read_lines(j("test.csv"))[:3]
    for model, artifact in (
            ("naiveBayes", {"bayesian.model.file.path": j("nb_model")}),
            ("knn", {"training.data.path": j("train.csv")})):
        b, _, _ = _batcher({**churn, **artifact, "serve.models": model,
                            "serve.bucket.sizes": "1,8",
                            "serve.flush.deadline.ms": "100"})
        try:
            oracle = [b.submit(model, ln) for ln in good]
            pend = [b.submit_nowait(model, ln)
                    for ln in [good[0], "too,few", good[1], good[2]]]
            assert pend[0].wait(30.0) == oracle[0]
            with pytest.raises(RequestError):
                pend[1].wait(30.0)
            assert [pend[2].wait(30.0), pend[3].wait(30.0)] == oracle[1:]
            assert b.counters.get(f"Serving.{model}", "errors") == 1
        finally:
            b.close()


def test_registry_config_errors(ws):
    with pytest.raises(ConfigError):
        ModelRegistry.from_conf(JobConfig({}))               # no serve.models
    with pytest.raises(ConfigError):
        ModelRegistry.from_conf(JobConfig({"serve.models": "hologram"}))
    with pytest.raises(ConfigError):                         # missing artifact
        ModelRegistry.from_conf(JobConfig({"serve.models": "naiveBayes"}))


# ---------------------------------------------------------------------------
# front ends
# ---------------------------------------------------------------------------

def test_http_frontend_score_health_stats(ws):
    j, churn = ws["j"], ws["churn"]
    b, _, _ = _batcher({**churn, "bayesian.model.file.path": j("nb_model"),
                        "serve.models": "naiveBayes",
                        "serve.bucket.sizes": "1,4"})
    lines = read_lines(j("test.csv"))[:5]
    singles = [b.submit("naiveBayes", ln) for ln in lines]
    with ScoreHTTPServer(b) as srv:
        host, port = srv.address
        base = f"http://{host}:{port}"

        def post(payload, expect_status=200):
            req = urllib.request.Request(
                f"{base}/score", data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        status, body = post({"model": "naiveBayes", "rows": lines})
        assert status == 200 and body["results"] == singles
        status, body = post({"model": "noSuch", "rows": lines[:1]})
        assert status == 404 and body["error"] == "UNKNOWN_MODEL"
        status, body = post({"rows": lines[:1]})
        assert status == 400
        with urllib.request.urlopen(f"{base}/healthz") as resp:
            health = json.loads(resp.read())
        assert health["status"] == "ok" and health["models"] == ["naiveBayes"]
        with urllib.request.urlopen(f"{base}/stats") as resp:
            stats = json.loads(resp.read())
        assert stats["naiveBayes"]["requests"] >= 10
        assert "p99_ms" in stats["naiveBayes"]
    b.close()


def test_queue_frontend_inproc_and_resp_socket(ws):
    """The RESP-list transport end to end: first over in-proc queues, then
    over real sockets against the fake Redis server — the reference's own
    Redis simulators can drive the scoring plane like the Storm path."""
    from test_resp import _FakeRedisHandler

    import socketserver

    from avenir_tpu.pipeline.resp import RedisListQueue
    from avenir_tpu.pipeline.streaming import InProcQueue

    j, churn = ws["j"], ws["churn"]
    b, _, _ = _batcher({**churn, "bayesian.model.file.path": j("nb_model"),
                        "serve.models": "naiveBayes",
                        "serve.bucket.sizes": "1,4"})
    lines = read_lines(j("test.csv"))[:4]
    singles = [b.submit("naiveBayes", ln) for ln in lines]

    def check_transport(requests, responses):
        fe = QueueScoreFrontend(b, requests, responses)
        for i, ln in enumerate(lines):
            requests.push(f"r{i},naiveBayes,{ln}")
        requests.push("r9,noSuchModel,x")
        requests.push("malformed-no-delims")
        assert fe.poll_once() == len(lines) + 2
        got = {}
        for msg in responses.drain():
            rid, _, rest = msg.partition(",")
            got[rid] = rest
        for i in range(len(lines)):
            assert got[f"r{i}"] == singles[i]
        assert got["r9"].startswith("ERR,UNKNOWN_MODEL")
        assert got["malformed-no-delims"].startswith("ERR,BAD_REQUEST")

    check_transport(InProcQueue(), InProcQueue())

    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0),
                                          _FakeRedisHandler)
    srv.daemon_threads = True
    import collections

    srv.lists = collections.defaultdict(collections.deque)
    srv.lock = threading.Lock()
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        host, port = srv.server_address
        check_transport(
            RedisListQueue("scoreRequestQueue", host=host, port=port),
            RedisListQueue("scoreResponseQueue", host=host, port=port))
    finally:
        srv.shutdown()
        srv.server_close()
        b.close()


# ---------------------------------------------------------------------------
# driver `serve` stage + replay flow control
# ---------------------------------------------------------------------------

def test_scoring_plane_stage_in_pipeline(ws):
    """Artifact handoff: a Pipeline trains NB then serves the test file
    through the ONLINE plane; the stage output is byte-identical to the
    batch predictor job's."""
    from avenir_tpu.pipeline.driver import Pipeline, Stage

    j, churn = ws["j"], ws["churn"]
    conf2 = JobConfig({**churn, "bayesian.model.file.path": j("nb_model")})
    get_job("BayesianPredictor").run(conf2, j("test.csv"), j("nb_pred2"))
    batch = read_lines(j("nb_pred2"))

    p = Pipeline(j("serve_ws"), JobConfig(dict(churn)))
    p.bind("train", j("train.csv"))
    p.bind("test", j("test.csv"))
    p.add(Stage("bayesianDistr", "BayesianDistribution", "train",
                "bayes_model"))
    p.add(Stage("serve", "ScoringPlane", "test", "scored",
                props={"serve.models": "naiveBayes",
                       "bayesian.model.file.path": "@bayes_model",
                       "serve.queue.depth": "16",
                       "serve.bucket.sizes": "1,4,16"},
                uses=("bayes_model",)))
    counters = p.run()
    assert read_lines(p.path("scored")) == batch
    serve_c = counters["serve"]
    assert serve_c.get("Serving.naiveBayes", "requests") == len(batch)
    assert serve_c.get("Serving.naiveBayes", "recompiles") == 0
    # queue depth 16 << 120 rows: replay flow control never sheds
    assert serve_c.get("Serving.naiveBayes", "shed") == 0
    assert serve_c.get("Serving.naiveBayes", "p99_us") > 0


# ---------------------------------------------------------------------------
# the RL loop reports through the same schema (satellite)
# ---------------------------------------------------------------------------

def test_rl_server_shares_serving_schema():
    from avenir_tpu.models import online_rl as orl
    from avenir_tpu.pipeline import streaming as st

    learner = orl.create_learner("intervalEstimator", ["a", "b"],
                                 {"min.reward.distr.sample": 5}, seed=3)
    srv = st.ReinforcementLearnerServer(
        learner, st.QueueEventSource(st.InProcQueue()),
        st.QueueRewardReader(st.InProcQueue()),
        st.QueueActionWriter(st.InProcQueue()), model_name="rlLoop")
    for i in range(20):
        srv.events.queue.push(f"ev{i},{i}")
    assert srv.run() == 20
    stats = srv.stats()
    assert set(stats) == {"rlLoop"}
    s = stats["rlLoop"]
    # the exact keys the scoring plane publishes (utils.metrics.serving_stats)
    assert s["requests"] == 20 and s["batches"] == 20 and s["bucket.1"] == 20
    assert s["latency_samples"] == 20 and s["p99_ms"] >= s["p50_ms"] >= 0.0


def test_latency_tracker_ring():
    from avenir_tpu.utils.metrics import LatencyTracker

    tr = LatencyTracker(capacity=8)
    assert tr.percentile(99) == 0.0
    for v in range(100):                  # old samples age out of the ring
        tr.record(v / 1000.0)
    assert tr.count == 100
    assert 0.092 <= tr.percentile(50) <= 0.099
    snap = tr.snapshot()
    assert snap["latency_samples"] == 100 and snap["p99_ms"] >= snap["p50_ms"]


# ---------------------------------------------------------------------------
# two dispatches in flight (PR 33)
# ---------------------------------------------------------------------------

class _GatedServable(ServableModel):
    """``score_lines`` blocks on an event of its own: a test sees each call
    enter (``entered``), holds it in flight for as long as it likes and
    releases it by its index (``release``)."""

    family = "gated"

    def __init__(self):
        super().__init__()
        self.calls = []                   # the lines of each call, by entry
        self.gates = []
        self.entered = threading.Semaphore(0)
        self._lock = threading.Lock()

    def score_lines(self, lines, pad_to):
        gate = threading.Event()
        with self._lock:
            self.calls.append(list(lines))
            self.gates.append(gate)
        self.entered.release()
        assert gate.wait(20.0), "the test never released this call"
        self.compile_keys.add((pad_to,))
        return [f"{line},ok" for line in lines]

    def warmup(self, pad_to):
        self.compile_keys.add((pad_to,))

    def wait_entered(self, timeout=5.0):
        return self.entered.acquire(timeout=timeout)

    def release(self, call):
        self.gates[call].set()


GATED_BUCKET = 4
GATED_DEADLINE_S = 0.005


def _gated(**kwargs):
    servable = _GatedServable()
    batcher = BucketedMicrobatcher(
        ModelRegistry().add("m", servable), bucket_sizes=(1, 2, GATED_BUCKET),
        flush_deadline_ms=GATED_DEADLINE_S * 1e3, request_timeout_ms=20_000.0,
        **kwargs)
    return servable, batcher


def _submit(batcher, tag, n):
    return [batcher.submit_nowait("m", f"{tag}{i}") for i in range(n)]


def _finish_all(servable, batcher, reqs):
    """Release everything still gated and collect every reply."""
    closer = threading.Thread(target=batcher.close)
    closer.start()
    deadline = time.monotonic() + 20.0
    while closer.is_alive() and time.monotonic() < deadline:
        for gate in list(servable.gates):
            gate.set()
        time.sleep(0.005)
    closer.join(5.0)
    assert not closer.is_alive()
    return [r.wait(5.0) for r in reqs]


def _dispatch_spans(journal_path):
    return [e["attrs"] for e in read_events(journal_path)
            if e.get("ev") == "span.close" and e.get("name") == "serve.dispatch"]


@pytest.fixture
def traced(tmp_path):
    tracer = tel.tracer().enable(str(tmp_path))
    try:
        yield tracer
    finally:
        tel.tracer().disable()


@pytest.mark.parametrize("second,overlaps", [(GATED_BUCKET, True),
                                             (GATED_BUCKET - 1, False)],
                         ids=["full-bucket-overlaps", "short-bucket-waits"])
def test_second_dispatch_in_flight_takes_only_a_full_bucket(
        traced, second, overlaps):
    """While one dispatch is in flight a second FULL bucket is taken at once
    (counter ``overlapped``, span attr ``inflight`` 1); a SHORT bucket past
    its flush deadline is not — it goes out the moment the dispatch in
    flight returns, which is when one dispatcher would have looked at it."""
    servable, b = _gated()
    reqs = _submit(b, "a", GATED_BUCKET)
    assert servable.wait_entered()
    reqs += _submit(b, "b", second)
    time.sleep(10 * GATED_DEADLINE_S)             # far past the deadline
    assert servable.wait_entered(timeout=0.2) is overlaps
    if not overlaps:
        assert b.queue_depths() == {"m": second}
        assert len(servable.calls) == 1
        servable.release(0)
        assert servable.wait_entered()            # taken as call 0 returns
    assert servable.calls[1] == [f"b{i}" for i in range(second)]
    outs = _finish_all(servable, b, reqs)
    assert outs == [f"{r.line},ok" for r in reqs]
    assert b.counters.get("Serving.m", "overlapped") == int(overlaps)
    assert b.counters.get("Serving.m", "batches") == 2
    spans = _dispatch_spans(traced.journal_path)
    assert sorted(s["inflight"] for s in spans) == [0, int(overlaps)]


def test_third_full_bucket_waits_for_a_dispatch_to_return():
    """Depth is two: with two dispatches in flight a third full bucket
    stays queued until one of them returns."""
    servable, b = _gated()
    reqs = _submit(b, "a", GATED_BUCKET)
    assert servable.wait_entered()
    reqs += _submit(b, "b", GATED_BUCKET)
    assert servable.wait_entered()
    reqs += _submit(b, "c", GATED_BUCKET)
    assert not servable.wait_entered(timeout=0.2)
    assert b.queue_depths() == {"m": GATED_BUCKET}
    assert len(b._blackbox_inflight()) == 3 * GATED_BUCKET
    servable.release(1)                           # either may return first
    assert servable.wait_entered()
    assert servable.calls[2] == [f"c{i}" for i in range(GATED_BUCKET)]
    outs = _finish_all(servable, b, reqs)
    assert outs == [f"{r.line},ok" for r in reqs]
    assert b.counters.get("Serving.m", "overlapped") == 2
    assert b.counters.get("Serving.m", "requests") == 3 * GATED_BUCKET


def test_straggler_rejoins_its_group_of_closed_loop_callers():
    """PERF.md §6, PR 26 finding 2, with 2 x bucket callers: one caller of
    the second group is late.  The three that wait are NOT cut at their
    deadline while the first group is in flight, so the straggler completes
    their bucket; and where a group was cut short (the straggler came after
    everything had returned) the next full bucket re-forms within two
    cycles, because a short bucket never goes out beside a dispatch in
    flight."""
    servable, b = _gated()
    first = _submit(b, "a", GATED_BUCKET)
    assert servable.wait_entered()                # group a in flight
    rest = _submit(b, "b", GATED_BUCKET - 1)
    time.sleep(10 * GATED_DEADLINE_S)
    assert len(servable.calls) == 1               # not cut at the deadline
    late = _submit(b, "late", 1)
    assert servable.wait_entered()
    assert servable.calls[1] == ["b0", "b1", "b2", "late0"]
    servable.release(0)
    servable.release(1)
    assert [r.wait(5.0) for r in first + rest + late]
    # cycle 2, the straggler later still: nothing is in flight when the
    # three pass their deadline, so they go out short (as with one
    # dispatcher) ...
    rest = _submit(b, "d", GATED_BUCKET - 1)
    assert servable.wait_entered()
    assert servable.calls[2] == ["d0", "d1", "d2"]
    first = _submit(b, "c", GATED_BUCKET)         # ... beside a full bucket,
    assert servable.wait_entered()
    late = _submit(b, "late", 1)                  # and the straggler waits
    time.sleep(10 * GATED_DEADLINE_S)
    assert len(servable.calls) == 4
    servable.release(2)                           # the three come back ...
    assert [r.wait(5.0) for r in rest]
    rest = _submit(b, "d", GATED_BUCKET - 1)      # ... and re-submit:
    assert servable.wait_entered()
    assert servable.calls[4] == ["late0", "d0", "d1", "d2"]   # full again
    outs = _finish_all(servable, b, first + late + rest)
    assert len(outs) == 2 * GATED_BUCKET


class _EchoServable(ServableModel):
    """Answers at once: the quiet model beside a gated, saturated one."""

    family = "echo"

    def score_lines(self, lines, pad_to):
        self.compile_keys.add((pad_to,))
        return [f"{line},ok" for line in lines]

    def warmup(self, pad_to):
        self.compile_keys.add((pad_to,))


def _gated_beside_quiet(quiet_first=False):
    """Two models in one batcher: ``x`` gated (the saturated one) with two
    dispatches in flight, and one answering at once (the quiet one): ``y``,
    or ``a`` where it is to be the first of the batcher's models (they are
    held by name)."""
    servable = _GatedServable()
    registry = ModelRegistry().add("x", servable).add(
        "a" if quiet_first else "y", _EchoServable())
    b = BucketedMicrobatcher(
        registry, bucket_sizes=(1, 2, GATED_BUCKET),
        flush_deadline_ms=GATED_DEADLINE_S * 1e3, request_timeout_ms=20_000.0)
    reqs = []
    for tag in "ab":
        reqs += [b.submit_nowait("x", f"{tag}{i}")
                 for i in range(GATED_BUCKET)]
        assert servable.wait_entered()
    return servable, b, reqs


@pytest.mark.parametrize("backlog", [0, 1],
                         ids=["x-in-flight", "x-in-flight-and-waiting"])
def test_quiet_models_short_bucket_is_not_held_by_a_saturated_neighbour(
        backlog):
    """The 'not while in flight' rule is per model.  ``x`` has a dispatch
    in flight from before ``y``'s request arrived until after it is
    answered (call 1 is never released meanwhile), with or without a third
    full bucket of ``x`` waiting: ``y``'s single request, past its deadline,
    is popped by the first thread that comes free — with ``x``'s waiting
    bucket, as one dispatcher pops one batch of every ready model — and
    answered at most one dispatch later; it gets no RequestTimeout and its
    caller does not hang."""
    servable, b, reqs = _gated_beside_quiet()
    reqs += [b.submit_nowait("x", f"c{i}")
             for i in range(backlog * GATED_BUCKET)]
    lone = b.submit_nowait("y", "q")
    time.sleep(10 * GATED_DEADLINE_S)
    assert b.queue_depths() == {"x": backlog * GATED_BUCKET, "y": 1}
    servable.release(0)                           # one thread comes free
    if backlog:
        assert servable.wait_entered()            # x's bucket c, popped with
        assert b.queue_depths() == {"x": 0, "y": 0}     # y's in one take
        servable.release(2)
    assert lone.wait(5.0) == "q,ok"
    assert not servable.gates[1].is_set()         # x was in flight all along
    assert b.counters.get("Serving.y", "overlapped") == 1
    assert b.counters.get("Serving.y", "batches") == 1
    outs = _finish_all(servable, b, reqs)
    assert outs == [f"{r.line},ok" for r in reqs]


@pytest.mark.parametrize("models", [2, 1],
                         ids=["quiet-model-first", "one-saturated-model"])
def test_probe_answers_on_a_healthy_saturated_replica(models):
    """``probe()`` queues a short bucket on the first model.  With two
    dispatches of ``x`` in flight it is answered as soon as a thread is
    free: alone where the first model is a quiet one, in ``x``'s next full
    bucket where ``x`` is the only model — a dispatch of ``x`` still in
    flight either way."""
    if models == 2:
        servable, b, reqs = _gated_beside_quiet(quiet_first=True)
    else:
        servable, b = _gated()
        reqs = []
        for tag in "ab":
            reqs += _submit(b, tag, GATED_BUCKET)
            assert servable.wait_entered()
    answer = []
    prober = threading.Thread(target=lambda: answer.append(b.probe(5.0)))
    prober.start()
    while not any(r["rid"] == "probe" for r in b._blackbox_inflight()):
        time.sleep(0.001)
    if models == 1:
        reqs += _submit(b, "c", GATED_BUCKET - 1)   # the probe's bucket fills
    servable.release(0)
    prober.join(5.0)
    assert answer == [True]
    assert not servable.gates[1].is_set()
    outs = _finish_all(servable, b, reqs)
    assert outs == [f"{r.line},ok" for r in reqs]
    assert b.counters.get("Serving.x" if models == 2 else "Serving.m",
                          "requests") == len(reqs)


def test_dispatch_fault_with_two_in_flight_fails_both_and_scores_once():
    """The ``serve.dispatch`` kill on the SECOND dispatch while the first is
    in flight: every unfinished request of both, and the queue, fails with
    the retryable ReplicaDownError; the fault fires before a row of its
    batch scores; the first dispatch's late replies are dropped (no request
    is reported scored after it was failed over); both threads end."""
    servable, b = _gated(fault=FaultPlan({"serve.dispatch": 2}))
    reqs = _submit(b, "a", GATED_BUCKET)
    assert servable.wait_entered()
    with b._cond:                                 # six at once: 4 + 2 queued
        reqs += _submit(b, "b", GATED_BUCKET + 2)
    for req in reqs:
        with pytest.raises(ReplicaDownError):
            req.wait(5.0)
    assert b.failed and b.queue_depths() == {"m": 0}
    with pytest.raises(ReplicaDownError):
        b.submit_nowait("m", "x")
    servable.release(0)                           # the survivor returns late
    for thread in b._threads:
        thread.join(5.0)
        assert not thread.is_alive()
    scored = [line for call in servable.calls for line in call]
    assert scored == [f"a{i}" for i in range(GATED_BUCKET)]
    assert b.counters.get("Serving.m", "requests") == 0
    assert b.counters.get("Serving.m", "batches") == 0
    assert not b.probe(0.2)
    b.close()


def test_stalled_reads_the_oldest_dispatch_in_flight():
    """One dispatch wedged, the other dispatcher beating: the batcher reads
    as stalled (the OLDEST heartbeat in flight counts, not the newest)."""
    servable, b = _gated()
    reqs = _submit(b, "a", GATED_BUCKET)
    assert servable.wait_entered()                # call 0: wedged
    time.sleep(0.3)
    more = _submit(b, "b", GATED_BUCKET)          # the other thread works on
    assert servable.wait_entered()
    servable.release(1)
    assert [r.wait(5.0) for r in more]
    assert time.monotonic() - b.heartbeat < 0.25  # it beat just now
    assert b.stalled(0.25)
    assert not b.stalled(30.0)
    assert not b.probe(0.25)                      # nor does its probe pass
    servable.release(0)
    assert [r.wait(5.0) for r in reqs]
    assert not b.stalled(0.0)                     # idle is never stalled
    b.close()


# ---------------------------------------------------------------------------
# the hand-over: a one-shot latch a request, the group released in one pass
# ---------------------------------------------------------------------------

@pytest.fixture
def eager_switches():
    """Hand the interpreter round every microsecond: two threads walking the
    same requests then meet inside ``finish`` thousands of times a run."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        yield
    finally:
        sys.setswitchinterval(old)


def _parked_batcher():
    """A batcher whose dispatcher threads have ended without touching a
    request: the test is the only one to reply, die or reap."""
    registry = ModelRegistry().add("m", _EchoServable())
    b = BucketedMicrobatcher(registry, bucket_sizes=(1, 64),
                             request_timeout_ms=60_000.0)
    with b._cond:
        b._halt = True
        b._cond.notify_all()
    for thread in b._threads:
        thread.join(5.0)
        assert not thread.is_alive()
    return registry.get("m"), b


@pytest.mark.parametrize("rival", ["fail_pending", "_die"])
def test_reply_raced_by_a_dying_replica_finishes_each_request_once(
        rival, eager_switches):
    """``_reply`` on one thread, ``fail_pending`` (the requests still
    queued) or ``_die`` (the requests of a dispatch in flight) on another,
    over the same 10 000 requests, a bucket of 64 at a time and both from
    the bucket's first: each is finished exactly once, by whoever popped
    its token; the loser's ``finish`` reports False and leaves ``result`` /
    ``error`` as the winner wrote them; what the reply won is what it
    counted and recorded."""
    n = 10_000
    entry, b = _parked_batcher()
    reqs = [PendingRequest("m", f"r{i}") for i in range(n)]
    chunks = [reqs[lo:lo + 64] for lo in range(0, n, 64)]
    flights = [_Flight([_Batch("m", chunk, [])], ahead=0) for chunk in chunks]
    start = threading.Barrier(2)

    def reply():
        for chunk, flight in zip(chunks, flights):
            start.wait()
            b._reply(entry, "Serving.m", "m", chunk,
                     [f"{r.line},ok" for r in chunk], 64, flight, None, ())

    def down():
        for chunk, flight in zip(chunks, flights):
            with b._cond:
                if rival == "fail_pending":
                    b._queues["m"].extend(chunk)
                else:
                    b._flights[:] = [flight]
            start.wait()
            getattr(b, rival)()

    threads = [threading.Thread(target=reply), threading.Thread(target=down)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
        assert not t.is_alive()
    scored = failed = 0
    for req in reqs:
        assert req.done()
        assert not req.finish(result="late")          # a done request is done
        if req.error is None:
            assert req.result == f"{req.line},ok" == req.wait(0)
            scored += 1
        else:
            assert isinstance(req.error, ReplicaDownError)
            assert req.result is None
            with pytest.raises(ReplicaDownError):
                req.wait(0)
            failed += 1
    assert scored + failed == n
    assert b.counters.get("Serving.m", "requests") == scored
    assert b.latency["m"].count == scored
    # a race, not a walk-over: both sides won some (a side that won none
    # would make this a test of one thread)
    assert scored and failed
    b.close()


def _interrupted(first, at, second, inbox, outbox):
    """Run ``first`` on this thread and, when it stands before the
    ``at``-th instruction of ``PendingRequest.finish``, run ``second``
    WHOLE on the worker thread behind ``inbox`` before going on: the
    interleaving a switch of the interpreter at that instruction gives.
    True where ``first`` got that far."""
    code = PendingRequest.finish.__code__
    seen = [0]
    ran = []

    def in_finish(frame, event, arg):
        if event == "opcode":
            if seen[0] == at:
                inbox.put(second)
                ran.append(outbox.get(timeout=30.0))
            seen[0] += 1
        return in_finish

    def on_call(frame, event, arg):
        if event == "call" and frame.f_code is code:
            frame.f_trace_opcodes = True
            sys.settrace(on_call)         # 3.12 honours the flag only so
            return in_finish
        return None

    sys.settrace(on_call)
    try:
        first()
    finally:
        sys.settrace(None)
    if not ran:                                       # past the last one
        inbox.put(second)
        outbox.get(timeout=30.0)
    return bool(ran)


@pytest.mark.parametrize("interrupted", ["reply", "rival"])
@pytest.mark.parametrize("rival", ["fail_pending", "_die"])
def test_finish_switched_at_every_instruction_still_has_one_winner(
        rival, interrupted):
    """The race above, made certain: one side is stopped before each
    instruction of ``finish`` in turn while the other side, on a second
    thread, finishes the same request whole.  Wherever the switch falls
    exactly one of the two wins, and the loser changes nothing."""
    entry, b = _parked_batcher()
    inbox, outbox = queue.SimpleQueue(), queue.SimpleQueue()

    def work():
        for fn in iter(inbox.get, None):
            outbox.put(fn() or True)

    worker = threading.Thread(target=work, daemon=True)
    worker.start()
    at, reached, n = 0, True, 0
    counted = 0
    while reached:                                    # until past the end
        req = PendingRequest("m", f"r{n}")
        flight = _Flight([_Batch("m", [req], [])], ahead=0)
        with b._cond:
            if rival == "fail_pending":
                b._queues["m"].append(req)
            else:
                b._flights[:] = [flight]

        def reply():
            b._reply(entry, "Serving.m", "m", [req], [f"{req.line},ok"], 1,
                     flight, None, ())

        sides = (reply, getattr(b, rival))
        if interrupted == "rival":
            sides = sides[::-1]
        reached = _interrupted(sides[0], at, sides[1], inbox, outbox)
        assert req.done() and not req.finish(result="late")
        scored = b.counters.get("Serving.m", "requests") - counted
        counted += scored
        if req.error is None:
            assert scored == 1 and req.wait(0) == f"{req.line},ok"
        else:
            assert scored == 0 and req.result is None
            assert isinstance(req.error, ReplicaDownError)
        # stopped before its first instruction the interrupted side loses,
        # stopped after its last it has won
        at += 1
        n += 1
    inbox.put(None)
    worker.join(5.0)
    assert n > 10                                     # finish is not empty
    assert b.latency["m"].count == counted and 0 < counted < n
    b.close()


@pytest.mark.parametrize("case", ["timeout-then-finish", "wait-twice",
                                  "second-finish-loses", "no-timeout",
                                  "error-raised-every-wait"])
def test_pending_request_latch(case):
    req = PendingRequest("m", "x")
    assert not req.done()
    if case == "timeout-then-finish":
        for timeout in (0.01, 0, -1.0):               # a past deadline is 0
            with pytest.raises(RequestTimeout):
                req.wait(timeout)
        assert not req.done()
        assert req.finish(result="late but whole")
        assert req.done() and req.wait(0) == "late but whole"
    elif case == "wait-twice":
        assert req.finish(result="ok")
        assert req.wait(1.0) == "ok" and req.wait(1.0) == "ok"
        assert req.wait() == "ok" and req.done()
    elif case == "second-finish-loses":
        assert req.finish(result="scored")
        assert not req.finish(error=ReplicaDownError("died"))
        assert not req.finish(result="again")
        assert req.result == "scored" and req.error is None
        assert req.wait(0) == "scored"
    elif case == "no-timeout":
        got = []
        waiter = threading.Thread(target=lambda: got.append(req.wait()))
        waiter.start()
        time.sleep(0.05)
        assert waiter.is_alive() and not got          # blocked in the latch
        assert req.finish(result="ok")
        waiter.join(5.0)
        assert got == ["ok"]
    else:
        err = ReplicaDownError("died")
        assert req.finish(error=err)
        for _ in range(2):
            with pytest.raises(ReplicaDownError) as raised:
                req.wait(1.0)
            assert raised.value is err
        assert req.done() and req.result is None


@pytest.mark.parametrize("capacity,chunks", [
    (8, [3, 3, 3, 3]),            # wraps inside the third chunk
    (8, [8]),                     # exactly the ring
    (8, [5, 20]),                 # more than the ring in one call
    (8, [0, 1, 0]),               # nothing to record
    (16, [4, 4]),                 # never wraps
    (1, [3]),
], ids=["wrap", "exact", "overrun", "empty", "short", "one-slot"])
def test_record_many_is_n_records(capacity, chunks):
    """One lock and one slice assignment leave the ring, ``count`` and the
    percentiles exactly as N ``record`` calls do, wrap-around included."""
    one, many = LatencyTracker(capacity), LatencyTracker(capacity)
    at = 0
    for n in chunks:
        vals = [(at + i + 1) / 1000.0 for i in range(n)]
        at += n
        for v in vals:
            one.record(v)
        many.record_many(vals)
        assert many.count == one.count == at
        assert (many._next, many._filled) == (one._next, one._filled)
        np.testing.assert_array_equal(many._buf, one._buf)
    for q in (0.0, 50.0, 95.0, 99.0, 100.0):
        assert many.percentile(q) == one.percentile(q)
    assert many.snapshot() == one.snapshot()


@pytest.mark.parametrize("aged,late", [
    ([0, 2], []),                 # the batch's first and another timed out
    ([0, 1, 2, 3], []),           # the whole batch
    ([], []),                     # nobody: no request is walked
    ([2], []),                    # the oldest is NOT the batch's first
    ([0], [1]),                   # a live one a hair under the limit
], ids=["oldest-and-another", "all", "none", "oldest-behind-a-younger",
        "oldest-beside-a-near-miss"])
def test_timeout_sweep_fails_every_timed_out_request_and_no_live_one(
        aged, late):
    """The time-out sweep looks at the oldest request once a dispatch and
    walks the batch only where that one has timed out: every request past
    ``serve.request.timeout.ms`` still fails typed, wherever it stands in
    the batch, and no live one does."""
    timeout_s = 5.0
    servable, b = _gated()
    b.request_timeout_s = timeout_s
    with b._cond:                                     # one take, one batch
        reqs = _submit(b, "a", GATED_BUCKET)
        for i in aged:
            reqs[i].enqueued -= 2 * timeout_s
        for i in late:
            reqs[i].enqueued -= timeout_s - 1.0
    live = [r for i, r in enumerate(reqs) if i not in aged]
    if live:
        assert servable.wait_entered()
        assert servable.calls[0] == [r.line for r in live]
        servable.release(0)
    for i, req in enumerate(reqs):
        if i in aged:
            with pytest.raises(RequestTimeout) as raised:
                req.wait(5.0)
            assert raised.value.queue_wait_ms > 2 * timeout_s * 1e3
        else:
            assert req.wait(5.0) == f"{req.line},ok"
    assert b.counters.get("Serving.m", "timeouts") == len(aged)
    b.close()
    assert b.counters.get("Serving.m", "requests") == len(live)
    assert len(servable.calls) == (1 if live else 0)


@pytest.mark.parametrize("rid,tenant", [("r-7", "alpha"), (None, None)],
                         ids=["rid-and-tenant", "bare"])
def test_flight_ring_submit_record_is_field_for_field_the_dict(rid, tenant):
    """The submit door hands the ring a tuple; the snapshot (what a
    SIGKILLed replica's bundle holds) reads as the dict it was, key for key
    and in the same order."""
    servable, b = _gated()
    blackbox.ring_clear()
    with tel.label_scope(tenant=tenant):
        first = b.submit_nowait("m", "x", rid=rid)
        second = b.submit_nowait("m", "y", rid=rid)
    recs = [r for r in blackbox.ring_snapshot() if r["ev"] == "serve.submit"]
    assert [list(r) for r in recs] == [
        ["ts", "ev", "rid", "model", "tenant", "depth"]] * 2
    assert [{k: v for k, v in r.items() if k != "ts"} for r in recs] == [
        {"ev": "serve.submit", "rid": rid, "model": "m", "tenant": tenant,
         "depth": depth} for depth in (1, 2)]
    assert all(isinstance(r["ts"], float) for r in recs)
    json.dumps(recs)                                  # a bundle serialises it
    outs = _finish_all(servable, b, [first, second])
    assert outs == ["x,ok", "y,ok"]


def test_group_is_released_before_its_bookkeeping(traced):
    """One pass releases a dispatch's requests, the latency samples and the
    ``serve.request`` spans are written after it — with the values of the
    one clock read before the pass: a span's ``wait_ms`` is its request's
    sample, and ``reply_passes`` counts the dispatch beside ``batches``."""
    servable, b = _gated()
    reqs = _submit(b, "a", GATED_BUCKET)
    assert servable.wait_entered()
    reqs += _submit(b, "b", GATED_BUCKET)
    outs = _finish_all(servable, b, reqs)
    assert outs == [f"{r.line},ok" for r in reqs]
    assert b.counters.get("Serving.m", "batches") == 2
    assert b.counters.get("Serving.m", "reply_passes") == 2
    tracker = b.latency["m"]
    assert tracker.count == len(reqs)
    samples = sorted(round(s * 1e3, 3) for s in tracker._buf[:len(reqs)])
    spans = [e for e in read_events(traced.journal_path)
             if e.get("ev") == "span.close" and e.get("name") == "serve.request"]
    assert sorted(e["attrs"]["wait_ms"] for e in spans) == samples
    assert sorted(e["dur_ms"] for e in spans) == samples
    # one clock read a dispatch: within a dispatch (four samples in a row in
    # the ring) two requests' waits differ by exactly what their enqueue
    # times do
    stamps = [np.sort([r.enqueued for r in reqs[lo:lo + GATED_BUCKET]])
              for lo in (0, GATED_BUCKET)]
    for lo in (0, GATED_BUCKET):
        waits = np.sort(tracker._buf[lo:lo + GATED_BUCKET])[::-1]
        assert any(np.ptp(waits + group) < 1e-6 for group in stamps)
