"""Job-level telemetry aggregation: one report over N ranks' planes.

Every rank of a ``launch --telemetry_port BASE`` job serves its own
``/metrics`` + ``/healthz`` + ``/ledger`` on ``BASE + rank``
(utils/telemetry.py) — but an operator asking "is the *job* healthy"
had to scrape and eyeball N endpoints.  fleetview is the zero-dependency
(stdlib urllib + the in-repo monitor parser) aggregator that merges them
into one job-level report:

* **cross-rank step-time skew + straggler attribution** — per-rank mean
  ``executor.step_time_ms`` reconstructed from the Prometheus histogram,
  stragglers flagged by the same leave-one-out-median rule the watchdog
  applies to heartbeat step lag, and **cross-checked** against the
  watchdog's own ``/healthz`` straggler verdict when a rank serves one
  (the two views agreeing is the acceptance bar: tests/test_fleetview.py
  injects a 5x straggler and pins identical attribution),
* **comm-bytes imbalance per mesh axis** — max/min of each rank's traced
  ``comm.allreduce_bytes`` totals,
* **goodput rollup** — min/mean of ``train.goodput_pct`` across ranks,
* **measured-vs-predicted calibration table** — ``/ledger`` records
  merged per (program x plan x mesh) key with latest + worst drift per
  cost model (utils/ledger.py bands attached),
* **job-level SLO alert plane** — ``/alerts`` scraped per rank and
  deduped by (slo, severity): one tenant's TTFT burning its budget on
  every rank is ONE job alert listing the affected ranks, not N pages.
  ``/history`` supplies per-rank ``slo.burn_rate`` series rendered as
  text-mode sparklines, and ``--gate`` makes the exit code non-zero
  while any job-level alert is firing — a CI job fails on burning SLOs
  like on any other regression,

in ``--format text`` / ``--format json`` / ``--watch`` modes.  The JSON
report carries a flat numeric ``record`` block (fleet skew, calibration
drift, alerts firing: one number each, for a gate to compare).  This
is also the scrape client ROADMAP item 4's serving-fleet router reuses.

Usage::

    python -m tools.fleetview --base-port 9100 --nranks 4
    python -m tools.fleetview --endpoints 127.0.0.1:9100,127.0.0.1:9101
    python -m tools.fleetview --base-port 9100 --nranks 4 --watch 5
    python -m tools.fleetview --selfcheck      # tier-1 CI: in-process servers
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

from paddle_tpu.utils import monitor as _monitor

__all__ = ["scrape_rank", "merge", "render_text", "selfcheck", "main"]

_DEF_TIMEOUT = 5.0
_SCRAPE_PATHS = ("/metrics", "/healthz", "/ledger", "/alerts", "/history")

# the fleet aggregator instruments itself through the same registry it
# scrapes from others (tools/metricsdump --lint inventories these)
_m_scrapes = _monitor.counter(
    "fleet.scrapes", "Rank telemetry scrapes attempted by fleetview, by "
    "endpoint path.", labelnames=("path",))
_m_scrape_errors = _monitor.counter(
    "fleet.scrape_errors", "Rank telemetry scrapes that failed (connection "
    "refused, bad body), by endpoint path.", labelnames=("path",))
_m_ranks = _monitor.gauge(
    "fleet.ranks", "Ranks merged into the last fleetview report.")


# ---------------------------------------------------------------------------
# Scraping one rank.
# ---------------------------------------------------------------------------
def _fetch(url: str, timeout: float) -> Tuple[int, str]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as e:
        # /healthz answers 503 with a full JSON body when degraded — that
        # is a *successful* scrape of an unhealthy rank, not an error
        return e.code, e.read().decode("utf-8", "replace")


def scrape_rank(endpoint: str, timeout: float = _DEF_TIMEOUT,
                since: int = 0) -> Dict[str, Any]:
    """Scrape one rank's /metrics + /healthz + /ledger + /alerts +
    /history.  Legs fail independently: a rank with a dead plane still
    appears in the merged report (with per-leg errors) instead of sinking
    the whole job view."""
    out: Dict[str, Any] = {"endpoint": endpoint}
    for path in _SCRAPE_PATHS:
        _m_scrapes.inc(path=path)
        key = path.strip("/")
        url = f"http://{endpoint}{path}"
        if path == "/ledger":
            url += f"?since={int(since)}&n=500"
        elif path == "/history":
            url += "?max_points=64"
        try:
            status, body = _fetch(url, timeout)
        except Exception as e:
            _m_scrape_errors.inc(path=path)
            out[key] = {"error": repr(e)}
            continue
        if path == "/metrics":
            try:
                out[key] = _monitor.parse_prometheus_text(body)
            except ValueError as e:
                _m_scrape_errors.inc(path=path)
                out[key] = {"error": repr(e)}
        else:
            try:
                doc = json.loads(body)
                doc["_status"] = status
                out[key] = doc
            except ValueError:
                _m_scrape_errors.inc(path=path)
                out[key] = {"error": f"bad json body (HTTP {status})"}
    return out


def _scrape_ok(leg: Any) -> bool:
    return isinstance(leg, dict) and "error" not in leg


# ---------------------------------------------------------------------------
# Prometheus-histogram reconstruction.
# ---------------------------------------------------------------------------
def _hist_stats(parsed: Dict[Tuple[str, tuple], float],
                prom_name: str) -> Optional[Dict[str, float]]:
    """mean/p50 of one exposed histogram, label cells aggregated.  The
    p50 is linearly interpolated inside the cumulative buckets — scrape-
    side reconstruction, the exact number a Prometheus `histogram_quantile`
    would compute."""
    total = count = 0.0
    buckets: Dict[float, float] = {}
    prefix_sum, prefix_count = prom_name + "_sum", prom_name + "_count"
    prefix_bucket = prom_name + "_bucket"
    for (name, labelitems), value in parsed.items():
        if name == prefix_sum:
            total += value
        elif name == prefix_count:
            count += value
        elif name == prefix_bucket:
            le = dict(labelitems).get("le", "+Inf")
            edge = float("inf") if le == "+Inf" else float(le)
            buckets[edge] = buckets.get(edge, 0.0) + value
    if count <= 0:
        return None
    target = 0.5 * count
    p50 = None
    lo_edge, lo_cum = 0.0, 0.0
    for edge in sorted(buckets):
        cum = buckets[edge]
        if cum >= target:
            if edge == float("inf") or cum <= lo_cum:
                p50 = lo_edge
            else:
                p50 = lo_edge + (edge - lo_edge) * (
                    (target - lo_cum) / (cum - lo_cum))
            break
        lo_edge, lo_cum = edge, cum
    return {"count": count, "mean": total / count,
            "p50": p50 if p50 is not None else total / count}


def _gauge_value(parsed: Dict[Tuple[str, tuple], float],
                 prom_name: str) -> Optional[float]:
    return parsed.get((prom_name, ()))


def _comm_axis_bytes(parsed: Dict[Tuple[str, tuple], float]
                     ) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for (name, labelitems), value in parsed.items():
        if name == "comm_allreduce_bytes_sum":
            axis = dict(labelitems).get("axis", "?")
            out[axis] = out.get(axis, 0.0) + value
    return out


# ---------------------------------------------------------------------------
# Merging.
# ---------------------------------------------------------------------------
def _median(values: List[float]) -> float:
    s = sorted(values)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _rank_ids(scrapes: List[Dict[str, Any]]) -> List[int]:
    """Trainer ranks from /healthz; scrape order is the fallback when
    ranks are missing or collide (e.g. --selfcheck's two servers in one
    process both report the process rank)."""
    ids = []
    for idx, s in enumerate(scrapes):
        h = s.get("healthz")
        ids.append(h.get("rank") if _scrape_ok(h) else None)
    if any(r is None for r in ids) or len(set(ids)) != len(ids):
        return list(range(len(scrapes)))
    return [int(r) for r in ids]


def merge(scrapes: List[Dict[str, Any]], straggler_factor: float = 2.0,
          min_skew_ms: float = 1.0) -> Dict[str, Any]:
    """Merge per-rank scrapes into one JSON-safe job-level report.

    Straggler rule = the watchdog's (utils/watchdog.py straggler_report):
    rank r is a straggler iff its mean step time exceeds
    ``straggler_factor x`` the leave-one-out median of the others, with
    ``min_skew_ms`` as the absolute floor so idle/fast fleets don't flag
    noise.  The report cross-checks this skew-derived verdict against the
    watchdog's own heartbeat-lag verdict scraped off /healthz."""
    ranks = _rank_ids(scrapes)
    report: Dict[str, Any] = {
        "schema": "fleetview/1",
        "nranks": len(scrapes),
        "ranks": {},
    }
    step_means: Dict[int, float] = {}
    step_p50s: List[float] = []
    goodputs: List[float] = []
    axis_bytes: Dict[str, Dict[int, float]] = {}
    healthy = 0
    wd_section = None

    for rank, s in zip(ranks, scrapes):
        row: Dict[str, Any] = {"endpoint": s.get("endpoint", "")}
        h = s.get("healthz")
        if _scrape_ok(h):
            row["status"] = h.get("status", "?")
            row["healthz_rank"] = h.get("rank")
            if h.get("_status") == 200:
                healthy += 1
            wd = h.get("watchdog")
            if (wd_section is None and isinstance(wd, dict)
                    and isinstance(wd.get("stragglers"), dict)):
                wd_section = {"source_rank": rank,
                              "stragglers": wd["stragglers"].get(
                                  "stragglers", []),
                              "front_step": wd["stragglers"].get(
                                  "front_step")}
        else:
            row["status"] = "unreachable"
            row["error"] = (h or {}).get("error")
        parsed = s.get("metrics")
        if _scrape_ok(parsed):
            st = (_hist_stats(parsed, "executor_step_time_ms")
                  or _hist_stats(parsed, "train_step_time_ms"))
            if st is not None:
                step_means[rank] = st["mean"]
                step_p50s.append(st["p50"])
                row["step_time_ms"] = {
                    "mean": round(st["mean"], 4),
                    "p50": round(st["p50"], 4),
                    "count": int(st["count"])}
            gp = _gauge_value(parsed, "train_goodput_pct")
            if gp is not None:
                goodputs.append(gp)
                row["goodput_pct"] = round(gp, 2)
            for axis, nbytes in _comm_axis_bytes(parsed).items():
                axis_bytes.setdefault(axis, {})[rank] = nbytes
        led = s.get("ledger")
        if _scrape_ok(led):
            row["ledger_records"] = len(led.get("records", []))
            row["ledger_truncated"] = bool(led.get("truncated"))
        report["ranks"][str(rank)] = row

    report["healthy_ranks"] = healthy

    # -- cross-rank step-time skew + straggler attribution ----------------
    stragglers: List[int] = []
    skew = None
    if step_means:
        med = _median(list(step_means.values()))
        skew = (max(step_means.values()) / med) if med > 0 else None
        for rank, mean in sorted(step_means.items()):
            others = [v for r, v in step_means.items() if r != rank]
            if not others:
                continue
            med_o = _median(others)
            if mean > max(min_skew_ms, straggler_factor * med_o):
                stragglers.append(rank)
    report["skew"] = {
        "step_time_mean_ms": {str(r): round(v, 4)
                              for r, v in sorted(step_means.items())},
        "max_over_median": round(skew, 4) if skew is not None else None,
        "straggler_factor": straggler_factor,
        "stragglers": stragglers,
    }

    # -- cross-check against the watchdog's heartbeat attribution ---------
    if wd_section is not None:
        wd_section["agrees"] = (
            sorted(int(r) for r in wd_section["stragglers"])
            == sorted(stragglers))
    report["watchdog"] = wd_section

    # -- comm-bytes imbalance per axis ------------------------------------
    imbalance: Dict[str, Any] = {}
    for axis, per_rank in sorted(axis_bytes.items()):
        hi, lo = max(per_rank.values()), min(per_rank.values())
        imbalance[axis] = {
            "bytes": {str(r): v for r, v in sorted(per_rank.items())},
            "max_over_min": round(hi / lo, 4) if lo > 0 else None,
        }
    report["comm_imbalance"] = imbalance

    # -- goodput rollup ----------------------------------------------------
    report["goodput"] = {
        "min_pct": round(min(goodputs), 2) if goodputs else None,
        "mean_pct": round(sum(goodputs) / len(goodputs), 2)
                    if goodputs else None,
    }

    # -- measured-vs-predicted calibration table --------------------------
    report["calibration"] = _calibration_table(scrapes)

    # -- job-level SLO alert dedupe + burn-rate history -------------------
    report["alerts"] = _alerts_section(scrapes, ranks)
    report["burn_history"] = _burn_history(scrapes, ranks)

    # -- flat numeric verdict --------------------------------------------
    record: Dict[str, Any] = {
        "fleet": {"nranks": len(scrapes), "healthy_ranks": healthy,
                  "stragglers": len(stragglers)},
        "slo": {"alerts_firing": len(report["alerts"]["firing"]),
                "pages_firing": sum(
                    1 for a in report["alerts"]["firing"]
                    if a["severity"] == "page")},
    }
    if skew is not None:
        record["fleet"]["step_time_skew"] = round(skew, 4)
    if step_p50s:
        record["fleet"]["step_time_p50_ms"] = round(_median(step_p50s), 4)
    if goodputs:
        record["fleet"]["goodput_min_pct"] = round(min(goodputs), 2)
        record["fleet"]["goodput_mean_pct"] = round(
            sum(goodputs) / len(goodputs), 2)
    comm_rec = {f"imbalance_{axis}": doc["max_over_min"]
                for axis, doc in imbalance.items()
                if doc["max_over_min"] is not None}
    if comm_rec:
        record["comm"] = comm_rec
    worst = report["calibration"].get("worst_drift", {})
    cal_rec = {f"{model}_drift": ratio for model, ratio in worst.items()
               if ratio is not None}
    if cal_rec:
        record["calibration"] = cal_rec
    report["record"] = record

    _m_ranks.set(len(scrapes))
    return report


def _calibration_table(scrapes: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Ledger records merged per (program x plan x mesh) key: latest
    predicted/measured legs, latest + worst drift per model, and the band
    violations seen — the table autoplan's measured-vs-predicted gate
    reads."""
    bands: Dict[str, Any] = {}
    table: Dict[str, Dict[str, Any]] = {}
    worst: Dict[str, Optional[float]] = {}
    for s in scrapes:
        led = s.get("ledger")
        if not _scrape_ok(led):
            continue
        if isinstance(led.get("bands"), dict):
            bands = led["bands"]
        for rec in led.get("records", []):
            key = rec.get("key") or {}
            kid = "|".join(str(key.get(k) or "-")
                           for k in ("program", "plan", "mesh"))
            row = table.setdefault(kid, {
                "key": key, "records": 0, "band_violations": 0,
                "predicted": {}, "measured": {}, "drift": {},
                "worst_drift": {}})
            row["records"] += 1
            row["band_violations"] += len(rec.get("band_violations") or ())
            for leg in ("predicted", "measured"):
                for k, v in (rec.get(leg) or {}).items():
                    if v is not None:
                        row[leg][k] = v
            for model, ratio in (rec.get("drift") or {}).items():
                if ratio is None:
                    continue
                row["drift"][model] = round(ratio, 4)
                prev = row["worst_drift"].get(model)
                row["worst_drift"][model] = round(
                    ratio if prev is None else max(prev, ratio), 4)
                w = worst.get(model)
                worst[model] = round(
                    ratio if w is None else max(w, ratio), 4)
    return {"bands": bands, "programs": table, "worst_drift": worst}


def _alerts_section(scrapes: List[Dict[str, Any]],
                    ranks: List[int]) -> Dict[str, Any]:
    """Per-rank /alerts legs deduped into job-level alerts: one entry per
    (slo, severity) in a non-ok state, listing which ranks report it and
    the worst burn rates seen — the job view an operator (or the --gate
    exit code) acts on."""
    job: Dict[Tuple[str, str], Dict[str, Any]] = {}
    reporting = 0
    for rank, s in zip(ranks, scrapes):
        al = s.get("alerts")
        if not _scrape_ok(al):
            continue
        reporting += 1
        for a in al.get("alerts", []):
            state = a.get("state", "ok")
            if state in ("ok",):
                continue
            key = (str(a.get("slo")), str(a.get("severity")))
            row = job.setdefault(key, {
                "slo": key[0], "severity": key[1], "state": state,
                "metric": a.get("metric"), "ranks": [],
                "burn_short": 0.0, "burn_long": 0.0})
            row["ranks"].append(rank)
            row["burn_short"] = max(row["burn_short"],
                                    float(a.get("burn_short") or 0.0))
            row["burn_long"] = max(row["burn_long"],
                                   float(a.get("burn_long") or 0.0))
            # firing on ANY rank makes the job alert firing; otherwise
            # keep the most advanced state seen (pending > resolved)
            order = {"resolved": 0, "pending": 1, "firing": 2}
            if order.get(state, 0) > order.get(row["state"], 0):
                row["state"] = state
    rows = [job[k] for k in sorted(job)]
    return {
        "ranks_reporting": reporting,
        "alerts": rows,
        "firing": [r for r in rows if r["state"] == "firing"],
    }


def _burn_history(scrapes: List[Dict[str, Any]], ranks: List[int],
                  max_points: int = 32) -> Dict[str, Dict[str, List[float]]]:
    """{burn-rate series: {rank: [values]}} off the /history legs — the
    sparkline data, also JSON-exported so dashboards can re-render it."""
    out: Dict[str, Dict[str, List[float]]] = {}
    for rank, s in zip(ranks, scrapes):
        hist = s.get("history")
        if not _scrape_ok(hist):
            continue
        for name, doc in (hist.get("series") or {}).items():
            if not name.startswith("slo.burn_rate{"):
                continue
            values = [float(p[2]) for p in (doc.get("samples") or [])]
            if values:
                out.setdefault(name, {})[str(rank)] = values[-max_points:]
    return out


_SPARK_GLYPHS = "▁▂▃▄▅▆▇█"


def _sparkline(values: List[float], width: int = 24) -> str:
    """Unicode sparkline, normalized to the series max (min pinned at 0 so
    a burn rate of 0 renders as the baseline glyph)."""
    if not values:
        return ""
    if len(values) > width:
        stride = len(values) / float(width)
        values = [values[min(len(values) - 1, int(i * stride))]
                  for i in range(width)]
    hi = max(max(values), 1e-12)
    return "".join(_SPARK_GLYPHS[min(len(_SPARK_GLYPHS) - 1,
                                     int(v / hi * (len(_SPARK_GLYPHS) - 1)))]
                   for v in values)


# ---------------------------------------------------------------------------
# Rendering.
# ---------------------------------------------------------------------------
def render_text(report: Dict[str, Any]) -> str:
    lines = [f"fleetview: {report['nranks']} ranks, "
             f"{report['healthy_ranks']} healthy"]
    lines.append(f"{'rank':>5} {'status':<12} {'step p50 ms':>12} "
                 f"{'mean ms':>10} {'goodput%':>9} {'ledger':>7}")
    for rank in sorted(report["ranks"], key=lambda r: int(r)):
        row = report["ranks"][rank]
        st = row.get("step_time_ms") or {}
        p50 = f"{st['p50']:.3f}" if st else "-"
        mean = f"{st['mean']:.3f}" if st else "-"
        gp = f"{row['goodput_pct']:.1f}" if "goodput_pct" in row else "-"
        led = str(row.get("ledger_records", "-"))
        lines.append(f"{rank:>5} {row.get('status', '?'):<12} {p50:>12} "
                     f"{mean:>10} {gp:>9} {led:>7}")
    skew = report["skew"]
    lines.append(f"skew: max/median="
                 f"{skew['max_over_median'] if skew['max_over_median'] is not None else '-'}"
                 f"  stragglers={skew['stragglers'] or 'none'}")
    wd = report.get("watchdog")
    if wd is not None:
        lines.append(f"watchdog (rank {wd['source_rank']}): "
                     f"stragglers={wd['stragglers'] or 'none'}  "
                     f"agrees={'yes' if wd['agrees'] else 'NO'}")
    for axis, doc in report["comm_imbalance"].items():
        lines.append(f"comm[{axis}]: max/min={doc['max_over_min']}")
    gp = report["goodput"]
    if gp["mean_pct"] is not None:
        lines.append(f"goodput: min={gp['min_pct']}%  mean={gp['mean_pct']}%")
    alerts = report.get("alerts") or {}
    if alerts.get("alerts"):
        lines.append(f"alerts ({alerts['ranks_reporting']} ranks "
                     "reporting):")
        for a in alerts["alerts"]:
            lines.append(
                f"  {a['state'].upper():<9} {a['slo']}:{a['severity']}  "
                f"burn={a['burn_short']:.1f}/{a['burn_long']:.1f}  "
                f"ranks={a['ranks']}")
    elif alerts.get("ranks_reporting"):
        lines.append(f"alerts: none firing "
                     f"({alerts['ranks_reporting']} ranks reporting)")
    for name, per_rank in sorted((report.get("burn_history") or {}).items()):
        for rank in sorted(per_rank, key=int):
            values = per_rank[rank]
            lines.append(f"  {name} r{rank} {_sparkline(values)} "
                         f"{values[-1]:.2f}")
    cal = report["calibration"]
    if cal["programs"]:
        lines.append(f"calibration ({len(cal['programs'])} programs, "
                     f"bands={cal['bands']}):")
        lines.append(f"  {'program':<24} {'model':>9} {'drift':>8} "
                     f"{'worst':>8} {'recs':>5} {'viol':>5}")
        for kid, row in sorted(cal["programs"].items()):
            prog = (row["key"].get("program") or kid)[:24]
            for model in sorted(row["drift"]):
                lines.append(
                    f"  {prog:<24} {model:>9} {row['drift'][model]:>8} "
                    f"{row['worst_drift'][model]:>8} {row['records']:>5} "
                    f"{row['band_violations']:>5}")
                prog = ""
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Selfcheck: the tier-1 CI smoke (no subprocesses, no fixed ports).
# ---------------------------------------------------------------------------
_REPORT_KEYS = ("schema", "nranks", "healthy_ranks", "ranks", "skew",
                "watchdog", "comm_imbalance", "goodput", "calibration",
                "alerts", "burn_history", "record")


def selfcheck(verbose: bool = True) -> int:
    """Spin two in-process telemetry servers over private registries (one
    seeded 5x slower), scrape them over real HTTP, and assert the merged
    report's schema + straggler verdict.  Exercises the full wire path —
    exposition, parse round-trip, histogram reconstruction, merge."""
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.utils import telemetry as _telemetry

    saved = {"metrics": _flags.get_flag("metrics")}
    _flags.set_flags({"metrics": True})
    servers = []
    try:
        for rank, step_ms in ((0, 10.0), (1, 50.0)):
            reg = _monitor.MetricRegistry()
            hist = reg.histogram("executor.step_time_ms",
                                 "selfcheck step times")
            for _ in range(20):
                hist.observe(step_ms)
            reg.gauge("train.goodput_pct",
                      "selfcheck goodput").set(90.0 - 10.0 * rank)
            reg.histogram(
                "comm.allreduce_bytes", "selfcheck comm",
                labelnames=("axis", "dtype"),
                buckets=(1 << 10, 1 << 20),
            ).observe(1024.0 * (rank + 1), axis="dp", dtype="fp32")
            servers.append(
                _telemetry.TelemetryServer(port=0, registry=reg).start())
        scrapes = [scrape_rank(f"127.0.0.1:{s.port}") for s in servers]
        report = merge(scrapes)

        missing = [k for k in _REPORT_KEYS if k not in report]
        assert not missing, f"report missing keys: {missing}"
        assert report["nranks"] == 2
        for rank in ("0", "1"):
            assert "step_time_ms" in report["ranks"][rank], \
                f"rank {rank} metrics did not survive the wire"
        assert report["skew"]["stragglers"] == [1], report["skew"]
        # 2 ranks at 10/50 ms: median 30, skew 50/30
        assert report["record"]["fleet"]["step_time_skew"] > 1.5
        assert report["record"]["fleet"]["stragglers"] == 1
        assert report["comm_imbalance"]["dp"]["max_over_min"] == 2.0
        assert report["goodput"]["min_pct"] == 80.0
        # both /ledger legs answered (global ledger; possibly empty)
        for rank in ("0", "1"):
            assert "ledger_records" in report["ranks"][rank]
        # both /alerts legs answered (global engine; possibly not running)
        assert report["alerts"]["ranks_reporting"] == 2, report["alerts"]
        assert "alerts_firing" in report["record"]["slo"]
        json.dumps(report)  # the whole report must be JSON-clean
        if verbose:
            print(json.dumps({"selfcheck": "pass",
                              "stragglers": report["skew"]["stragglers"],
                              "skew": report["skew"]["max_over_median"]}))
        return 0
    finally:
        for s in servers:
            s.stop()
        _flags.set_flags(saved)


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------
def _endpoints(args) -> List[str]:
    if args.endpoints:
        return [e.strip() for e in args.endpoints.split(",") if e.strip()]
    if args.base_port:
        return [f"{args.host}:{args.base_port + r}"
                for r in range(args.nranks)]
    raise SystemExit("fleetview: need --endpoints or --base-port/--nranks")


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="tools.fleetview",
        description="Aggregate N ranks' telemetry planes into one "
                    "job-level report")
    parser.add_argument("--endpoints", type=str, default="",
                        help="explicit host:port list, comma-separated")
    parser.add_argument("--base-port", "--base_port", type=int, default=0,
                        dest="base_port",
                        help="scrape base_port + r for r in range(nranks) "
                        "(the launch --telemetry_port contract)")
    parser.add_argument("--nranks", type=int, default=1)
    parser.add_argument("--host", type=str, default="127.0.0.1")
    parser.add_argument("--format", choices=("text", "json"),
                        default="text")
    parser.add_argument("--timeout", type=float, default=_DEF_TIMEOUT)
    parser.add_argument("--watch", type=float, default=0.0, metavar="SEC",
                        help="re-scrape and re-render every SEC seconds")
    parser.add_argument("--out", type=str, default="",
                        help="also write the JSON report to this path")
    parser.add_argument("--selfcheck", action="store_true",
                        help="spin 2 in-process servers, scrape, assert "
                        "the merged report (CI smoke)")
    parser.add_argument("--gate", action="store_true",
                        help="exit non-zero (3) while any job-level SLO "
                        "alert is firing — a CI job fails on burning "
                        "SLOs")
    args = parser.parse_args(argv)

    if args.selfcheck:
        return selfcheck()

    endpoints = _endpoints(args)
    while True:
        scrapes = [scrape_rank(e, timeout=args.timeout) for e in endpoints]
        report = merge(scrapes)
        if args.format == "json":
            print(json.dumps(report, indent=2, sort_keys=True))
        else:
            print(render_text(report), end="")
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=2, sort_keys=True)
        if args.gate and report["alerts"]["firing"]:
            names = [f"{a['slo']}:{a['severity']}"
                     for a in report["alerts"]["firing"]]
            print(f"fleetview: gate FAILED — firing: {', '.join(names)}",
                  file=sys.stderr)
            return 3
        if not args.watch:
            return 0
        try:
            time.sleep(args.watch)
        except KeyboardInterrupt:
            return 0
        print()


if __name__ == "__main__":
    sys.exit(main())
