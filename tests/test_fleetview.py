"""Fleet view (tools/fleetview.py): job-level telemetry aggregation.

The acceptance bar is the 3-rank ``launch --telemetry_port`` integration
test: an injected 5x straggler rank must be attributed identically by
fleetview's histogram-derived skew view and the watchdog's heartbeat-lag
view (``report["watchdog"]["agrees"]``), and the merged report's flat
``record`` block must carry the same verdict as numbers.  The merge
unit tests pin degraded-fleet behavior (unreachable ranks, disagreeing
watchdog) on synthetic scrapes; ``--selfcheck`` rides tier-1 both
in-process and as the CLI subprocess.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from tools import fleetview

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# synthetic-scrape helpers (merge() consumes scrape_rank()'s shape)
# ---------------------------------------------------------------------------
def _scrape(rank, step_ms, count=20, goodput=95.0, comm_dp=None,
            watchdog=None, ledger_records=()):
    parsed = {
        ("executor_step_time_ms_sum", ()): step_ms * count,
        ("executor_step_time_ms_count", ()): float(count),
        ("train_goodput_pct", ()): goodput,
    }
    if comm_dp is not None:
        parsed[("comm_allreduce_bytes_sum",
                (("axis", "dp"), ("dtype", "fp32")))] = comm_dp
    healthz = {"status": "ok", "rank": rank, "_status": 200}
    if watchdog is not None:
        healthz["watchdog"] = watchdog
    return {
        "endpoint": f"127.0.0.1:{9100 + rank}",
        "metrics": parsed,
        "healthz": healthz,
        "ledger": {"_status": 200, "last_seq": len(ledger_records),
                   "truncated": False,
                   "bands": {"comm": 2.0, "mem": 1.5, "roofline": None},
                   "records": list(ledger_records)},
    }


def test_merge_skew_straggler_and_record_block():
    report = fleetview.merge([_scrape(0, 10.0), _scrape(1, 50.0),
                              _scrape(2, 10.0)])
    assert report["nranks"] == 3 and report["healthy_ranks"] == 3
    assert report["skew"]["stragglers"] == [1]
    assert report["skew"]["max_over_median"] == pytest.approx(5.0)
    assert report["ranks"]["1"]["step_time_ms"]["mean"] == 50.0
    rec = report["record"]["fleet"]
    assert rec["stragglers"] == 1 and rec["step_time_skew"] == 5.0
    assert rec["goodput_min_pct"] == 95.0
    json.dumps(report)


def test_merge_tolerates_unreachable_rank():
    dead = {"endpoint": "127.0.0.1:9103",
            "metrics": {"error": "ConnectionRefusedError(111)"},
            "healthz": {"error": "ConnectionRefusedError(111)"},
            "ledger": {"error": "ConnectionRefusedError(111)"}}
    report = fleetview.merge([_scrape(0, 10.0), dead])
    assert report["nranks"] == 2 and report["healthy_ranks"] == 1
    row = report["ranks"]["1"]
    assert row["status"] == "unreachable" and "error" in row
    assert "step_time_ms" not in row
    # one live rank: no leave-one-out baseline, no false straggler
    assert report["skew"]["stragglers"] == []
    json.dumps(report)


def test_merge_watchdog_cross_check_agrees_and_disagrees():
    wd = {"stragglers": {"front_step": 120, "stragglers": [1],
                         "ranks": {}}}
    report = fleetview.merge([_scrape(0, 10.0, watchdog=wd),
                              _scrape(1, 50.0), _scrape(2, 10.0)])
    assert report["watchdog"]["source_rank"] == 0
    assert report["watchdog"]["stragglers"] == [1]
    assert report["watchdog"]["agrees"] is True
    # a heartbeat view naming a different rank must be flagged, not hidden
    wd_bad = {"stragglers": {"front_step": 120, "stragglers": [2],
                             "ranks": {}}}
    report = fleetview.merge([_scrape(0, 10.0, watchdog=wd_bad),
                              _scrape(1, 50.0), _scrape(2, 10.0)])
    assert report["watchdog"]["agrees"] is False
    # no rank serving a watchdog section -> explicit None, not a crash
    report = fleetview.merge([_scrape(0, 10.0), _scrape(1, 50.0)])
    assert report["watchdog"] is None


def test_merge_comm_imbalance_and_calibration_table():
    led = [{"seq": 1, "kind": "compile",
            "key": {"program": "pfc", "plan": None, "mesh": None},
            "predicted": {"peak_hbm_bytes": 120.0},
            "measured": {"mem_total_bytes": 100.0},
            "drift": {"comm": None, "mem": 1.2, "roofline": None},
            "band_violations": []},
           {"seq": 2, "kind": "window",
            "key": {"program": "pfc", "plan": None, "mesh": None},
            "predicted": {}, "measured": {"step_time_ms": 3.0},
            "drift": {"mem": 1.4}, "band_violations": []}]
    report = fleetview.merge([
        _scrape(0, 10.0, comm_dp=4096.0, ledger_records=led),
        _scrape(1, 12.0, comm_dp=1024.0)])
    assert report["comm_imbalance"]["dp"]["max_over_min"] == 4.0
    assert report["record"]["comm"]["imbalance_dp"] == 4.0
    cal = report["calibration"]
    assert cal["bands"]["mem"] == 1.5
    row = cal["programs"]["pfc|-|-"]
    assert row["records"] == 2
    assert row["drift"]["mem"] == 1.4          # latest
    assert row["worst_drift"]["mem"] == 1.4    # worst across records
    assert cal["worst_drift"]["mem"] == 1.4
    assert report["record"]["calibration"]["mem_drift"] == 1.4
    assert report["ranks"]["0"]["ledger_records"] == 2
    # text renderer covers the populated report end-to-end
    text = fleetview.render_text(report)
    assert "calibration" in text and "comm[dp]" in text


# ---------------------------------------------------------------------------
# selfcheck: tier-1 CI, in-process and as the CLI
# ---------------------------------------------------------------------------
def test_selfcheck_in_process():
    assert fleetview.selfcheck(verbose=False) == 0


def test_fleetview_cli_selfcheck():
    r = subprocess.run(
        [sys.executable, "-m", "tools.fleetview", "--selfcheck"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["selfcheck"] == "pass" and doc["stragglers"] == [1]


def test_cli_requires_endpoints():
    with pytest.raises(SystemExit):
        fleetview.main(["--format", "json"])


# ---------------------------------------------------------------------------
# the acceptance integration: 3 ranks, one injected 5x straggler, both
# attribution views agree, the flat record block says the same
# ---------------------------------------------------------------------------
def _free_port_base():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_launch_three_ranks_straggler_attributed_by_both_views(tmp_path):
    from paddle_tpu.distributed.launch import launch

    out = tmp_path / "out"
    hb = tmp_path / "hb"
    out.mkdir()
    hb.mkdir()
    base = _free_port_base()
    report_path = out / "report.json"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import json, os, time
        import paddle_tpu  # bootstrap starts this rank's telemetry plane
        from paddle_tpu.elastic.membership import ElasticMember
        from paddle_tpu.utils import ledger, monitor, telemetry, watchdog

        OUT = {str(out)!r}
        HB = {str(hb)!r}
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        srv = telemetry.get_server()
        assert srv is not None and srv.port == {base} + rank, srv

        member = ElasticMember(HB, rank=rank, world_size=3,
                               interval_s=0.05, dead_after_s=60.0).start()
        wd = watchdog.Watchdog(heartbeat_dir=HB)
        telemetry.register_health_provider("watchdog", wd.report)
        # one calibration record per rank so the merged /ledger table has
        # real legs to aggregate
        ledger.ledger().append(
            "compile", {{"program": "itest", "plan": None, "mesh": None}},
            {{"peak_hbm_bytes": 120.0}}, {{"mem_total_bytes": 100.0}})

        def wait_all(stem, deadline_s=30):
            deadline = time.time() + deadline_s
            while time.time() < deadline:
                if all(os.path.exists(os.path.join(OUT, stem % r))
                       for r in range(3)):
                    return True
                time.sleep(0.05)
            return False

        # start barrier: heartbeat step lag must measure per-step speed,
        # not the ranks' import-time skew
        open(os.path.join(OUT, "boot.%d" % rank), "w").close()
        assert wait_all("boot.%d"), "boot barrier timed out"

        STEP_MS = 50.0 if rank == 1 else 10.0   # rank 1 is the 5x straggler
        hist = monitor.histogram("executor.step_time_ms", "")
        step = 0
        deadline = time.time() + 1.2
        while time.time() < deadline:
            time.sleep(STEP_MS / 1000.0)
            step += 1
            hist.observe(STEP_MS)
            wd.observe_step(step, STEP_MS)
            member.set_step(step)

        open(os.path.join(OUT, "ready.%d" % rank), "w").close()
        assert wait_all("ready.%d"), "ready barrier timed out"

        if rank == 0:
            time.sleep(0.3)   # let every rank's final heartbeat land
            from tools import fleetview
            scrapes = [fleetview.scrape_rank("127.0.0.1:%d" % ({base} + r))
                       for r in range(3)]
            report = fleetview.merge(scrapes)
            tmp = os.path.join(OUT, ".report.tmp")
            with open(tmp, "w") as f:
                json.dump(report, f)
            os.replace(tmp, {str(report_path)!r})
        else:
            deadline = time.time() + 30
            while (time.time() < deadline
                   and not os.path.exists({str(report_path)!r})):
                time.sleep(0.1)
        member.stop()
    """))
    rc = launch(str(script), [], nproc=3, telemetry_port=base,
                backend_env=f"JAX_PLATFORMS=cpu,PYTHONPATH={REPO},"
                            "PDTPU_FLAGS_metrics=1")
    assert rc == 0
    report = json.load(open(report_path))

    # both attribution views name exactly the injected straggler
    assert report["nranks"] == 3 and report["healthy_ranks"] == 3
    assert report["skew"]["stragglers"] == [1]
    assert report["watchdog"]["stragglers"] == [1]
    assert report["watchdog"]["agrees"] is True
    assert report["skew"]["max_over_median"] > 2.0   # 50ms vs 10ms means
    # per-rank planes survived the wire: step means ordered as injected
    means = {r: report["ranks"][r]["step_time_ms"]["mean"]
             for r in ("0", "1", "2")}
    assert means["1"] > 2 * max(means["0"], means["2"])
    # goodput rollup came from the live watchdog gauges
    assert report["goodput"]["min_pct"] is not None
    # the merged calibration table joined every rank's /ledger leg
    cal = report["calibration"]
    assert cal["programs"]["itest|-|-"]["records"] == 3
    assert cal["worst_drift"]["mem"] == pytest.approx(1.2)

    # the flat numeric verdict, as written to disk
    record = report["record"]
    assert record["fleet"]["stragglers"] == 1
    assert record["fleet"]["step_time_skew"] > 2.0
    assert record["calibration"]["mem_drift"] == pytest.approx(1.2)


# ---------------------------------------------------------------------------
# the job-level alert plane: dedupe, state precedence, sparklines, gate
# ---------------------------------------------------------------------------
def _alerts_leg(state, slo_name="s", severity="page", burn_short=5.0,
                burn_long=3.0):
    return {"_status": 200, "alerts": [
        {"slo": slo_name, "severity": severity, "state": state,
         "metric": "t.m", "burn_short": burn_short,
         "burn_long": burn_long}]}


def test_alerts_section_dedupes_and_state_precedence():
    s0 = {"endpoint": "e0", "alerts": _alerts_leg("resolved",
                                                  burn_short=1.0)}
    s1 = {"endpoint": "e1", "alerts": _alerts_leg("firing", burn_short=9.0)}
    sec = fleetview._alerts_section([s0, s1], [0, 1])
    assert sec["ranks_reporting"] == 2
    (row,) = sec["alerts"]                   # ONE job alert, not two
    assert row["state"] == "firing"          # firing on ANY rank wins
    assert row["ranks"] == [0, 1]
    assert row["burn_short"] == 9.0          # worst burn survives the merge
    assert sec["firing"] == [row]
    # ok states are dropped; pending beats resolved; different (slo,
    # severity) pairs stay separate rows
    s2 = {"endpoint": "e0", "alerts": {"_status": 200, "alerts": [
        {"slo": "s", "severity": "page", "state": "ok"},
        {"slo": "q", "severity": "ticket", "state": "pending",
         "burn_short": 2.0, "burn_long": 2.0}]}}
    s3 = {"endpoint": "e1", "alerts": _alerts_leg(
        "resolved", slo_name="q", severity="ticket", burn_short=0.1,
        burn_long=0.1)}
    sec = fleetview._alerts_section([s2, s3], [0, 1])
    (row,) = sec["alerts"]
    assert (row["slo"], row["state"]) == ("q", "pending")
    assert sec["firing"] == []
    # an unreachable /alerts leg is skipped, never a crash
    dead = {"endpoint": "e", "alerts": {"error": "ConnectionRefused"}}
    sec = fleetview._alerts_section([dead], [0])
    assert sec == {"ranks_reporting": 0, "alerts": [], "firing": []}


def test_burn_history_and_sparkline():
    scr = {"endpoint": "e", "history": {"_status": 200, "series": {
        "slo.burn_rate{slo=s,window=5s}": {
            "samples": [[1, 0.0, 0.5], [2, 1.0, 2.0]]},
        "t.other": {"samples": [[3, 0.0, 1.0]]}}}}
    bh = fleetview._burn_history([scr], [0])
    assert list(bh) == ["slo.burn_rate{slo=s,window=5s}"]
    assert bh["slo.burn_rate{slo=s,window=5s}"]["0"] == [0.5, 2.0]
    # sparklines: empty-safe, normalized to the series max, width-thinned
    assert fleetview._sparkline([]) == ""
    line = fleetview._sparkline([0.0, 0.0, 8.0])
    assert len(line) == 3
    assert line[0] == fleetview._SPARK_GLYPHS[0]
    assert line[-1] == fleetview._SPARK_GLYPHS[-1]
    assert len(fleetview._sparkline([float(i) for i in range(100)],
                                    width=24)) == 24


def test_merge_alerts_ride_report_record_and_text():
    s0 = _scrape(0, 10.0)
    s1 = _scrape(1, 10.0)
    s0["alerts"] = _alerts_leg("firing")
    s1["alerts"] = _alerts_leg("firing")
    s0["history"] = {"_status": 200, "series": {
        "slo.burn_rate{slo=s,window=5s}": {
            "samples": [[1, 0.0, 0.0], [2, 1.0, 6.0]]}}}
    report = fleetview.merge([s0, s1])
    assert report["alerts"]["ranks_reporting"] == 2
    assert report["alerts"]["alerts"][0]["ranks"] == [0, 1]
    assert report["record"]["slo"] == {"alerts_firing": 1,
                                       "pages_firing": 1}
    text = fleetview.render_text(report)
    assert "FIRING" in text and "s:page" in text
    assert "slo.burn_rate{slo=s,window=5s}" in text
    # ranks without /alerts legs (older planes) degrade to an empty section
    empty = fleetview.merge([_scrape(0, 10.0)])
    assert empty["alerts"] == {"ranks_reporting": 0, "alerts": [],
                               "firing": []}
    assert empty["record"]["slo"]["alerts_firing"] == 0
    json.dumps(report)
