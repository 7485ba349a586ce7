"""Live ops plane tests (obs/ops_plane.py + the telemetry sink seam).

The ISSUE 13 acceptance contract: during a REAL CPU train and a live
``PredictionServer``, an HTTP scrape of ``/metrics`` returns valid
Prometheus text whose training/serve counters advance between scrapes,
``/healthz`` transitions warming -> ready, and ``/drain`` flushes
in-flight requests with exactly-once delivery preserved.  Plus the
disabled-cost guarantee: plane off => no thread, no socket, no sink,
and the PR 2 span fast path untouched; plane on => zero extra device
dispatches (span-count proof) and zero post-warmup recompiles under
the trace contract.
"""
import io
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import health, ops_plane
from lightgbm_tpu.obs import telemetry as tmod
from lightgbm_tpu.obs.ops_plane import RollingQuantiles


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    ops_plane.shutdown()
    health._set_active(False)
    obs.reset()


def _small_data(n=600, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    return X, y


def _scrape(port, path="/metrics"):
    """-> (status, body); 4xx/5xx bodies are read, not raised."""
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# one Prometheus text-format sample line: name{labels} value (a span
# of tens of microseconds, as a traced jit's, reads 3.03e-05)
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+=\"[^\"]*\""
    r"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})? -?[0-9.eE+\-naif]+$")


def _assert_valid_prometheus(body):
    lines = [ln for ln in body.splitlines() if ln.strip()]
    assert lines, "empty exposition"
    for ln in lines:
        if ln.startswith("#"):
            assert re.match(r"^# (TYPE|HELP) ", ln), ln
        else:
            assert _PROM_LINE.match(ln), f"invalid Prometheus line: {ln!r}"


def _counter_value(body, name):
    for ln in body.splitlines():
        if ln.startswith(name + " "):
            return float(ln.split()[-1])
    return None


# ---------------------------------------------------------------------------
# registry units
# ---------------------------------------------------------------------------
def test_rolling_quantiles_bounded():
    sk = RollingQuantiles(cap=100)
    for i in range(10_000):
        sk.observe(float(i))
    # all-time count, bounded window over the LAST cap samples
    assert sk.count == 10_000
    assert sk.window() == 100
    q = sk.quantiles()
    assert 9_900 <= q[50.0] <= 9_999
    assert q[50.0] <= q[99.0] <= q[99.9] <= 9_999
    st = sk.stats_ms()
    assert st["count"] == 10_000
    assert st["p999"] >= st["p99"] >= st["p50"] > 0


def test_prometheus_render_valid_and_complete():
    reg = ops_plane.MetricsRegistry()
    reg.counter("serve.requests", 1, 42)
    reg.gauge("gbdt.iterations", 7)
    reg.gauge("non.numeric", "text")        # JSON-only, must not render
    reg.event("health:stall", 2)
    for v in (0.001, 0.002, 0.5):
        reg.span("serve.batch", v)
    body = reg.render_prometheus()
    _assert_valid_prometheus(body)
    assert "lgbm_tpu_serve_requests_total 42" in body
    assert "lgbm_tpu_gbdt_iterations 7" in body
    assert "non_numeric" not in body
    assert 'lgbm_tpu_events_total{family="health",name="stall"} 2' in body
    assert 'lgbm_tpu_span_seconds_count{span="serve_batch"} 3' in body
    assert 'lgbm_tpu_health_state{state=' in body


# ---------------------------------------------------------------------------
# the live surface: real train + live server
# ---------------------------------------------------------------------------
def test_live_scrape_during_real_train(monkeypatch):
    """The acceptance core: scrape /metrics + /healthz WHILE a real
    CPU train runs — valid Prometheus text, training counters that
    advance between scrapes, warming -> ready."""
    monkeypatch.setenv("LGBM_TPU_OPS_PORT", "0")
    # per-iteration dispatches: every iteration closes spans + advances
    # counters, so mid-train scrapes see live movement
    monkeypatch.setenv("LGBM_TPU_NO_BLOCK", "1")
    plane = ops_plane.mount("test")     # pre-mount: the port is known
    assert plane is not None
    scrapes, states = [], []
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            code, body = _scrape(plane.port)
            hcode, hbody = _scrape(plane.port, "/healthz")
            scrapes.append(body)
            states.append((hcode, json.loads(hbody)["state"]))
            time.sleep(0.002)

    t = threading.Thread(target=poll, daemon=True)
    t.start()
    try:
        X, y = _small_data()
        ds = lgb.Dataset(X, label=y)
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbose": -1}, ds, num_boost_round=40)
    finally:
        stop.set()
        t.join(10)
    # final state of the surface, after the run
    code, body = _scrape(plane.port)
    assert code == 200
    _assert_valid_prometheus(body)
    hcode, hbody = _scrape(plane.port, "/healthz")
    final = json.loads(hbody)
    assert hcode == 200 and final["state"] == "ready"
    assert "train" in final["owners"]
    # warming was observable before the first window landed, ready after
    seen = [s for _, s in states]
    assert "warming" in seen, seen
    assert seen.index("warming") < len(seen) - 1
    # training counters advanced BETWEEN scrapes (live, not post-hoc)
    vals = [_counter_value(b, "lgbm_tpu_gbdt_dispatch_gaps_total")
            for b in scrapes + [body]]
    distinct = {v for v in vals if v is not None}
    assert len(distinct) >= 2, f"counter never advanced: {distinct}"
    # span sketches fed by the telemetry sink
    assert 'lgbm_tpu_span_seconds_count{span="gbdt_iteration"}' in body


def test_live_server_scrape_and_drain(monkeypatch):
    """Serve half of the acceptance: serve counters advance between
    scrapes, and /drain stops intake, flushes in-flight requests, and
    preserves exactly-once delivery."""
    monkeypatch.setenv("LGBM_TPU_OPS_PORT", "0")
    from lightgbm_tpu.serve import PredictionServer, compile_model
    X, y = _small_data(n=1_000)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 15})
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbose": -1}, ds, num_boost_round=3)
    cm = compile_model(bst)
    srv = PredictionServer(cm, max_batch=256, max_wait_ms=1.0,
                           buckets=(64, 256), min_bucket=64,
                           raw_score=True)
    plane = ops_plane.plane()
    assert plane is not None and "serve" in plane.owners
    futs = [srv.submit(X[i % 500:][:3]) for i in range(40)]
    for fu in futs:
        fu.result(60)
    _, body1 = _scrape(plane.port)
    v1 = _counter_value(body1, "lgbm_tpu_serve_requests_total")
    assert v1 is not None and v1 >= 40
    futs += [srv.submit(X[i % 500:][:2]) for i in range(25)]
    # in-flight work submitted; drain over HTTP must flush it all
    code, dbody = _scrape(plane.port, "/drain")
    assert code == 200
    drain = json.loads(dbody)
    assert drain["drained"] is True
    rep = drain["reports"][0]
    assert rep["drained"] is True
    assert rep["pending"] == 0
    assert rep["resolved"] == 65            # exactly once, all of them
    assert rep["failed"] == 0
    # every future resolved with a real result
    for fu in futs:
        assert fu.done() and fu.exception() is None
    # drained server refuses new work
    with pytest.raises(RuntimeError):
        srv.submit(X[:1])
    _, body2 = _scrape(plane.port)
    v2 = _counter_value(body2, "lgbm_tpu_serve_requests_total")
    assert v2 is not None and v2 > v1       # advanced between scrapes
    _assert_valid_prometheus(body2)
    # p99.9 rides the rolling sketch in the server's own stats
    for rec in rep["latency_ms"].values():
        assert rec["p999"] >= rec["p99"] >= rec["p50"] >= 0.0


def test_unknown_path_404(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_OPS_PORT", "0")
    plane = ops_plane.mount("test")
    code, body = _scrape(plane.port, "/nope")
    assert code == 404
    assert "/metrics" in json.loads(body)["paths"]


# ---------------------------------------------------------------------------
# disabled-cost guarantee
# ---------------------------------------------------------------------------
def test_disabled_no_thread_no_socket_no_sink(monkeypatch):
    """Ops plane off: mount is a None no-op — no HTTP thread, no
    sink installed, and the PR 2 disabled span fast path untouched
    (the shared no-op object, no per-call allocation)."""
    monkeypatch.delenv("LGBM_TPU_OPS_PORT", raising=False)
    assert ops_plane.mount("train") is None
    assert ops_plane.plane() is None
    assert tmod._sink is None
    assert not [t for t in threading.enumerate()
                if t.name == "lgbm-tpu-ops"]
    s1, s2 = obs.span("x"), obs.span("y", attr=1)
    assert s1 is s2 is tmod._NOOP_SPAN
    # enabled-but-unmounted telemetry: counter path sees a None sink
    obs.enable()
    obs.counter_add("c")
    assert tmod._sink is None


def test_plane_on_zero_extra_dispatches_and_recompiles(
        monkeypatch, tmp_path):
    """Span-count proof: the identical training config dispatches the
    SAME number of device programs with the plane mounted as without
    (the plane is host-side mirroring only), and the run stays zero
    post-warmup recompiles under the trace contract."""
    dispatch_spans = ("gbdt.block", "gbdt.block_compile", "gbdt.iteration")

    def _train_counts():
        X, y = _small_data(seed=3)
        ds = lgb.Dataset(X, label=y)
        obs.enable()
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbose": -1}, ds, num_boost_round=8)
        spans = obs.summary()["spans"]
        return {k: spans.get(k, {}).get("count", 0)
                for k in dispatch_spans}

    monkeypatch.delenv("LGBM_TPU_OPS_PORT", raising=False)
    baseline = _train_counts()
    obs.reset()
    monkeypatch.setenv("LGBM_TPU_OPS_PORT", "0")
    monkeypatch.setenv("LGBM_TPU_TRACE_CONTRACT", "1")
    with_plane = _train_counts()
    assert ops_plane.plane() is not None    # it really mounted
    assert with_plane == baseline, (with_plane, baseline)
    rep = obs.summary()["trace_contract"]
    assert rep["steady_ok"] is True
    assert rep["compiles_steady"] == 0


# ---------------------------------------------------------------------------
# multi-rank health lift + report rendering
# ---------------------------------------------------------------------------
def test_merged_summary_lifts_per_rank_health():
    from lightgbm_tpu.io.distributed import ThreadedAllgather
    obs.enable()
    health._set_active(True)
    health.mark_warming("train")
    health.mark_degraded("nonfinite", window=4)
    ag = ThreadedAllgather(1).for_rank(0)
    merged = obs.merged_summary(ag)
    assert merged["health"]["ranks"] == ["degraded"]
    assert merged["health"]["worst"] == "degraded"
    assert json.loads(json.dumps(merged)) == merged


def test_telemetry_report_health_section():
    from tools.telemetry_report import report_summary
    s = {"rank": 0, "process_count": 1, "spans": {},
         "counters": {"watchdog.arms": 3, "watchdog.fires": 1,
                      "health.sentinel_checks": 5,
                      "health.nonfinite": 1},
         "events": {"health:stall": 1, "health:nonfinite": 1},
         "health": {"state": "stalled",
                    "detail": {"stalled_span": "gbdt.block"}}}
    out = io.StringIO()
    report_summary(s, out=out)
    text = out.getvalue()
    assert "== health ==" in text
    assert "state: stalled" in text
    assert "stalled_span=gbdt.block" in text
    assert "watchdog: 3 arm(s), 1 fire(s)" in text
    assert "sentinels: 5 check(s), 1 trip(s)" in text
    assert "health:stall" in text
