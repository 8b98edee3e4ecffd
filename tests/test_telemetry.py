"""Live telemetry plane (utils/telemetry.py): HTTP exposition of metrics /
health / flight ring / xprof / spans / calibration ledger, and per-rank
servers under `launch --telemetry_port`.

The server smoke here is the tier-1 CI gate the ISSUE requires: start,
scrape /metrics + /healthz, round-trip the exposition through
``parse_prometheus_text``.  All servers bind ephemeral ports on 127.0.0.1
and run daemon threads, so pytest never hangs on shutdown."""
import json
import os
import textwrap
import time
import urllib.error
import urllib.request

import pytest

from paddle_tpu.core import flags
from paddle_tpu.utils import monitor, telemetry, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def _server():
    srv = telemetry.TelemetryServer(port=0).start()
    yield srv
    srv.stop()


def _get(port, path, timeout=10.0):
    """(status, json-or-text body) — reads error bodies too."""
    url = f"http://127.0.0.1:{port}{path}"
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            body = r.read().decode()
            status = r.status
    except urllib.error.HTTPError as e:
        body = e.read().decode()
        status = e.code
    try:
        return status, json.loads(body)
    except ValueError:
        return status, body


# ---------------------------------------------------------------------------
# endpoint smoke (the tier-1 CI gate)
# ---------------------------------------------------------------------------

def test_metrics_endpoint_round_trips_prometheus_text(_server):
    c = monitor.counter("t.telemetry_smoke", "scrape marker")
    c.inc(7)
    status, text = _get(_server.port, "/metrics")
    assert status == 200
    parsed = monitor.parse_prometheus_text(text)
    assert parsed[("t_telemetry_smoke", ())] == 7.0
    # the plane's own instruments ride the same exposition: scrape again so
    # the first scrape's request counter is visible
    status, text = _get(_server.port, "/metrics")
    parsed = monitor.parse_prometheus_text(text)
    assert parsed[("telemetry_requests", (("path", "/metrics"),))] >= 1.0
    assert parsed[("telemetry_port", ())] == float(_server.port)


def test_healthz_ok_and_degraded(_server):
    status, doc = _get(_server.port, "/healthz")
    assert status == 200
    assert doc["status"] == "ok"
    assert doc["pid"] == os.getpid()
    assert doc["uptime_s"] >= 0
    # a health provider reporting unhealthy flips the endpoint to 503
    telemetry.register_health_provider(
        "t_probe", lambda: {"healthy": False, "detail": "synthetic"})
    try:
        status, doc = _get(_server.port, "/healthz")
        assert status == 503
        assert doc["status"] == "degraded"
        assert doc["t_probe"]["detail"] == "synthetic"
        # a RAISING provider degrades to its repr, never a dead probe
        telemetry._health_providers["t_probe"] = lambda: 1 / 0
        status, doc = _get(_server.port, "/healthz")
        assert status == 200
        assert "ZeroDivisionError" in doc["t_probe"]["error"]
    finally:
        telemetry._health_providers.pop("t_probe", None)


def test_flight_and_spans_endpoints(_server):
    fr = trace.flight_recorder()
    seq0 = fr.last_seq
    fr.record("t_marker", name="telemetry_test", payload=42)
    with trace.span("t::span_probe"):
        pass
    status, doc = _get(_server.port, "/flight")
    assert status == 200
    kinds = [e["kind"] for e in doc["events"]]
    assert "t_marker" in kinds
    status, doc = _get(_server.port, f"/spans?since={seq0}&n=10")
    assert status == 200
    names = [e["name"] for e in doc["spans"]]
    assert names.count("t::span_probe") == 2        # begin + end
    assert all(e["kind"].startswith("span_") for e in doc["spans"])
    assert doc["last_seq"] >= seq0 + 3
    status, doc = _get(_server.port, "/spans?n=zebra")
    assert status == 400


def test_spans_truncated_when_cursor_falls_behind_ring(_server):
    """A poller whose ?since= cursor was overwritten past the bounded ring
    gets an explicit truncated:true, never a silent gap."""
    fr = trace.flight_recorder()
    seq0 = fr.last_seq
    status, doc = _get(_server.port, f"/spans?since={seq0}")
    assert status == 200 and doc["truncated"] is False   # nothing missed yet
    size = int(flags.get_flag("flight_recorder_size"))
    for i in range(size + 32):                           # wrap the ring
        fr.record("t_spin", name=f"e{i}")
    status, doc = _get(_server.port, f"/spans?since={seq0}")
    assert status == 200 and doc["truncated"] is True
    # a cursor at the live head is whole again
    status, doc = _get(_server.port, f"/spans?since={fr.last_seq}")
    assert status == 200
    assert doc["truncated"] is False and doc["spans"] == []


def test_ledger_endpoint_cursor_and_truncation(_server):
    from paddle_tpu.utils import ledger

    ledger.reset()
    try:
        led = ledger.ledger()
        led.append("compile", {"program": "t_led"},
                   {"peak_hbm_bytes": 130.0}, {"mem_total_bytes": 100.0})
        status, doc = _get(_server.port, "/ledger")
        assert status == 200
        assert doc["truncated"] is False and doc["last_seq"] == 1
        assert doc["bands"]["mem"] == 1.5                # bands ride along
        (rec,) = doc["records"]
        assert rec["kind"] == "compile"
        assert rec["drift"]["mem"] == pytest.approx(1.3)
        # incremental poll from the head: empty, not truncated
        status, doc = _get(_server.port, f"/ledger?since={led.last_seq}")
        assert status == 200
        assert doc["records"] == [] and doc["truncated"] is False
        # wrap the 256-record ring: the stale cursor is told explicitly
        for i in range(300):
            led.append("window", {"program": f"w{i}"}, {}, {})
        status, doc = _get(_server.port, "/ledger?since=1")
        assert status == 200 and doc["truncated"] is True
        assert len(doc["records"]) <= 256
        status, doc = _get(_server.port, "/ledger?since=zebra")
        assert status == 400
    finally:
        ledger.reset()


def test_xprof_endpoint_404_then_published(_server):
    telemetry._snapshots.pop("xprof", None)
    status, doc = _get(_server.port, "/xprof")
    assert status == 404 and "error" in doc
    telemetry.publish_snapshot("xprof", {"regions": [], "mfu": 0.5})
    status, doc = _get(_server.port, "/xprof")
    assert status == 200
    assert doc["doc"]["mfu"] == 0.5
    assert doc["published_at"] <= time.time()


def test_unknown_endpoint_404_lists_routes(_server):
    status, doc = _get(_server.port, "/nope")
    assert status == 404
    assert "/metrics" in doc["endpoints"]
    status, body = _get(_server.port, "/")
    assert status == 200 and "/healthz" in body


def test_healthz_reads_elastic_membership(_server, tmp_path):
    from paddle_tpu.elastic.membership import ElasticMember

    m = ElasticMember(str(tmp_path), rank=0, world_size=2,
                      interval_s=0.05, dead_after_s=30.0).start()
    try:
        status, doc = _get(_server.port, "/healthz")
        assert status == 200
        assert doc["elastic"]["rank"] == 0
        assert 0 in doc["elastic"]["live"]
        assert doc["elastic"]["heartbeat_age_s"]["0"] < 30.0
    finally:
        m.stop()
    # stopped member deregisters; healthz drops the section cleanly
    status, doc = _get(_server.port, "/healthz")
    assert status == 200


def test_singleton_start_idempotent_and_env_bootstrap():
    try:
        srv = telemetry.start_telemetry(port=0)
        assert telemetry.start_telemetry() is srv          # idempotent
        assert telemetry.get_server() is srv
        port = srv.port
        assert port > 0
    finally:
        telemetry.stop_telemetry()
    assert telemetry.get_server() is None
    # start_from_env: no env, flag 0 -> stays off
    os.environ.pop(telemetry.TELEMETRY_PORT_ENV, None)
    assert telemetry.start_from_env() is None
    # bind conflict: flight-recorded, returns None, never raises
    srv = telemetry.TelemetryServer(port=0).start()
    try:
        os.environ[telemetry.TELEMETRY_PORT_ENV] = str(srv.port)
        seq0 = trace.flight_recorder().last_seq
        assert telemetry.start_from_env() is None
        assert any(e["kind"] == "telemetry_bind_failed"
                   for e in trace.flight_recorder().events_since(seq0))
    finally:
        os.environ.pop(telemetry.TELEMETRY_PORT_ENV, None)
        srv.stop()


# ---------------------------------------------------------------------------
# launch --telemetry_port: per-rank live planes, self- and peer-scraped
# ---------------------------------------------------------------------------

def _free_port_base():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_launch_two_ranks_serve_live_metrics_and_healthz(tmp_path):
    from paddle_tpu.distributed.launch import launch

    out_dir = tmp_path / "out"
    out_dir.mkdir()
    base = _free_port_base()
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import json, os, time, urllib.request
        import paddle_tpu  # import bootstrap starts this rank's plane
        from paddle_tpu.utils import monitor, telemetry

        rank = int(os.environ["PADDLE_TRAINER_ID"])
        srv = telemetry.get_server()
        assert srv is not None and srv.port == {base} + rank, srv
        monitor.counter("t.worker_mark", "").inc(rank + 1)

        def scrape(port, path, tries=50):
            last = None
            for _ in range(tries):
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{{port}}{{path}}",
                            timeout=5) as r:
                        return r.status, r.read().decode()
                except Exception as e:  # peer may still be booting
                    last = e
                    time.sleep(0.2)
            raise last

        # self-scrape + peer-scrape (ports are deterministic: base + rank)
        peer = {base} + (1 - rank)
        results = {{}}
        for label, port in (("self", srv.port), ("peer", peer)):
            st, text = scrape(port, "/metrics")
            parsed = monitor.parse_prometheus_text(text)
            hst, hbody = scrape(port, "/healthz")
            results[label] = {{
                "metrics_status": st,
                "mark": parsed.get(("t_worker_mark", ()), None),
                "telemetry_port": parsed.get(("telemetry_port", ()), None),
                "healthz_status": hst,
                "healthz": json.loads(hbody),
            }}
        with open(os.path.join({str(out_dir)!r}, f"r{{rank}}.json"),
                  "w") as f:
            json.dump(results, f)

        # keep this rank's plane up until BOTH ranks finished scraping —
        # exiting early would refuse the peer's in-flight scrape
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(os.path.exists(os.path.join({str(out_dir)!r},
                                               f"r{{r}}.json"))
                   for r in (0, 1)):
                break
            time.sleep(0.1)
    """))
    rc = launch(str(script), [], nproc=2, telemetry_port=base,
                backend_env=f"JAX_PLATFORMS=cpu,PYTHONPATH={REPO},"
                            "PDTPU_FLAGS_metrics=1")
    assert rc == 0
    for rank in range(2):
        doc = json.load(open(out_dir / f"r{rank}.json"))
        for label in ("self", "peer"):
            r = doc[label]
            assert r["metrics_status"] == 200, (rank, label)
            assert r["healthz_status"] == 200, (rank, label)
            assert r["healthz"]["status"] == "ok"
        # self-scrape sees this rank's own counter and bound port
        assert doc["self"]["mark"] == float(rank + 1)
        assert doc["self"]["telemetry_port"] == float(base + rank)
        # peer-scrape proves BOTH planes were live simultaneously and
        # expose per-rank state (the peer's counter differs)
        assert doc["peer"]["telemetry_port"] == float(base + (1 - rank))
        assert doc["peer"]["mark"] == float((1 - rank) + 1)
        assert doc["peer"]["healthz"]["rank"] == 1 - rank


# ---------------------------------------------------------------------------
# teardown hygiene + concurrent scrapes (the SLO-engine plane rides here)
# ---------------------------------------------------------------------------

def test_stop_telemetry_resets_providers_and_snapshots():
    """stop_telemetry is full teardown: a restarted plane must not
    resurrect the dead session's health providers or snapshots."""
    srv = telemetry.start_telemetry(port=0)
    telemetry.register_health_provider(
        "t_stale", lambda: {"healthy": False, "detail": "stale"})
    telemetry.publish_snapshot("xprof", {"mfu": 0.1})
    status, _ = _get(srv.port, "/healthz")
    assert status == 503
    telemetry.stop_telemetry()
    assert telemetry.get_server() is None
    srv2 = telemetry.start_telemetry(port=0)
    try:
        status, doc = _get(srv2.port, "/healthz")
        assert status == 200 and "t_stale" not in doc
        status, _ = _get(srv2.port, "/xprof")
        assert status == 404
    finally:
        telemetry.stop_telemetry()
    telemetry.stop_telemetry()                    # idempotent
    # per-instance TelemetryServer.stop() deliberately does NOT clear the
    # process-wide provider registry (embedded servers share it)
    telemetry.register_health_provider("t_keep", lambda: {"healthy": True})
    try:
        telemetry.TelemetryServer(port=0).start().stop()
        assert "t_keep" in telemetry._health_providers
    finally:
        telemetry._health_providers.pop("t_keep", None)


def test_concurrent_scrapes_with_live_writer(_server):
    """Scrape threads hammer /metrics + /alerts + /history while a writer
    records and the history sampler ticks: every response parses (no torn
    prometheus text), no non-200, and /history's seq stays monotonic."""
    import threading

    from paddle_tpu.utils import slo

    slo.reset()
    try:
        eng = slo.engine()
        eng.register(slo.SLO("t-conc", "t.conc_gauge", ">", 1e9))
        c = monitor.counter("t.conc_ctr", "")
        g = monitor.gauge("t.conc_gauge", "")
        h = monitor.histogram("t.conc_hist", "")
        stop = threading.Event()
        errors = []

        def writer():
            i = 0
            while not stop.is_set():
                i += 1
                c.inc()
                g.set(float(i % 7))
                h.observe(float(i % 13))
                eng.tick()
                time.sleep(0.001)

        def scraper():
            last_seq = 0
            while not stop.is_set():
                try:
                    st, text = _get(_server.port, "/metrics")
                    assert st == 200
                    parsed = monitor.parse_prometheus_text(text)
                    assert parsed
                    st, doc = _get(_server.port, "/alerts")
                    assert st == 200 and doc["firing"] == []
                    st, doc = _get(_server.port, "/history?max_points=16")
                    assert st == 200
                    assert doc["last_seq"] >= last_seq
                    last_seq = doc["last_seq"]
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(e)
                    return

        threads = [threading.Thread(target=writer)] + \
            [threading.Thread(target=scraper) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors[:3]
        assert not any(t.is_alive() for t in threads)
    finally:
        slo.reset()
        telemetry._health_providers.pop("slo", None)
