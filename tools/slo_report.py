#!/usr/bin/env python
"""Offline cluster SLO/goodput report.

Renders the trace plane's artifacts — saved ``/metrics`` expositions
and/or a merged cluster Perfetto trace (``export_cluster_trace``) —
into one operator-readable report: per-replica goodput (slo_ok rate),
violation split (queued-too-long vs slow-service — the autoscaler's
"add replicas vs the engine is slow" signal), queue/service time
percentiles estimated from the histogram buckets, router placement
reasons, and per-trace-id request journeys (attempt > 1 = failover).

Usage:
    curl -s localhost:8100/metrics > /tmp/cluster.prom
    python tools/slo_report.py --metrics /tmp/cluster.prom \
        [--trace /tmp/cluster_trace.json]

Import-light on purpose (stdlib + numpy via telemetry's parser): the
post-mortem tool must run on a box with no jax. Exit 0 on success, 1
when a given artifact is missing/invalid.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
from collections import defaultdict

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LBL = re.compile(r'^(?P<fam>[a-zA-Z_:][a-zA-Z0-9_:]*)'
                  r'(?:\{(?P<labels>.*)\})?$')


def _labels(s):
    if not s:
        return {}
    return dict(re.findall(r'(\w+)="([^"]*)"', s))


def _percentile_from_buckets(buckets, q):
    """Histogram percentile estimate from cumulative (le, count) pairs
    — same linear-in-bucket interpolation as telemetry.LogHistogram,
    reconstructed from the text exposition."""
    pts = sorted(((le, c) for le, c in buckets if le != float("inf")))
    total = max((c for _, c in buckets), default=0)
    if not total:
        return None
    target = (q / 100.0) * total
    prev_le, prev_c = 0.0, 0
    for le, c in pts:
        if c >= target:
            span = c - prev_c
            frac = (target - prev_c) / span if span else 1.0
            return prev_le + frac * (le - prev_le)
        prev_le, prev_c = le, c
    return pts[-1][0] if pts else None


def report_metrics(path, out):
    from paddle_tpu.inference.telemetry import parse_prometheus
    try:
        with open(path) as f:
            samples = parse_prometheus(f.read())
    except (OSError, ValueError) as e:
        out.append(f"slo_report: cannot read metrics {path!r}: {e}")
        return 1
    per = defaultdict(dict)          # replica -> key -> value
    hists = defaultdict(list)        # (replica, family) -> [(le, cum)]
    reasons = {}
    for name, value in samples.items():
        m = _LBL.match(name)
        if not m:
            continue
        fam, lb = m.group("fam"), _labels(m.group("labels"))
        rep = lb.get("replica", "-")
        if fam == "paddle_gateway_route_decisions_total":
            reasons[lb.get("reason", "?")] = int(value)
        elif fam.endswith("_bucket") and "le" in lb:
            le = float("inf") if lb["le"] == "+Inf" else float(lb["le"])
            hists[(rep, fam[:-len("_bucket")])].append((le, value))
        elif fam in ("paddle_serving_slo_ok_total",
                     "paddle_serving_slo_violated_queue_total",
                     "paddle_serving_slo_violated_service_total",
                     "paddle_serving_requests_finished_total"):
            per[rep][fam] = int(value)

    out.append(f"== SLO / goodput ({os.path.basename(path)}) ==")
    for rep in sorted(r for r in per if per[r]):
        m = per[rep]
        ok = m.get("paddle_serving_slo_ok_total", 0)
        vq = m.get("paddle_serving_slo_violated_queue_total", 0)
        vs = m.get("paddle_serving_slo_violated_service_total", 0)
        done = ok + vq + vs
        goodput = (100.0 * ok / done) if done else None
        line = (f"  {rep}: goodput "
                + (f"{goodput:.1f}%" if goodput is not None else "n/a")
                + f" ({ok} ok, {vq} queued-too-long, {vs} slow-service"
                f" of {done})")
        # reconcile against the independent finished counter — a
        # mismatch means finished requests escaped SLO classification
        fin = m.get("paddle_serving_requests_finished_total")
        if fin is not None and fin != done:
            line += (f"  [RECONCILIATION BROKE: {done} classified != "
                     f"{fin} finished]")
        for fam, label in (
                ("paddle_serving_queue_time_seconds", "queue"),
                ("paddle_serving_service_time_seconds", "service")):
            b = hists.get((rep, fam))
            if b:
                p50 = _percentile_from_buckets(b, 50)
                p99 = _percentile_from_buckets(b, 99)
                if p50 is not None:
                    line += (f"; {label} p50/p99 "
                             f"{p50 * 1e3:.1f}/{p99 * 1e3:.1f} ms")
        out.append(line)
    if reasons:
        total = sum(reasons.values())
        out.append(f"  router decisions ({total}): " + ", ".join(
            f"{k}={v}" for k, v in sorted(reasons.items()) if v))
    return 0


def report_trace(path, out):
    from paddle_tpu.inference.telemetry import validate_chrome_trace
    try:
        doc = validate_chrome_trace(path)
    except (OSError, ValueError) as e:
        out.append(f"slo_report: invalid cluster trace {path!r}: {e}")
        return 1
    evs = doc["traceEvents"]
    pids = {e["pid"]: e["args"]["name"] for e in evs
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    journeys = defaultdict(lambda: {"spans": 0, "attempts": set(),
                                    "replicas": set(), "http": 0,
                                    "decisions": []})
    for e in evs:
        args = e.get("args") or {}
        tid = args.get("trace_id")
        if tid is None:
            continue
        j = journeys[tid]
        if e.get("pid") == 0:
            if str(e.get("name", "")).startswith("decision"):
                j["decisions"].append(args.get("reason"))
            elif e.get("ph") == "X":
                j["http"] += 1
        elif e.get("ph") == "X" and "attempt" in args:
            j["spans"] += 1
            j["attempts"].add(args["attempt"])
            j["replicas"].add(pids.get(e["pid"], e["pid"]))
    out.append(f"== cluster trace ({os.path.basename(path)}: "
               f"{len(evs)} events, {len(pids)} processes) ==")
    failovers = [t for t, j in journeys.items()
                 if j["attempts"] and max(j["attempts"]) > 1]
    out.append(f"  traced requests: {len(journeys)}; with failover "
               f"re-submits: {len(failovers)}")
    for t in sorted(failovers)[:10]:
        j = journeys[t]
        out.append(f"  {t}: attempts {sorted(j['attempts'])} over "
                   f"{sorted(j['replicas'])}; decisions "
                   f"{j['decisions']}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python tools/slo_report.py",
        description="offline cluster SLO/goodput report")
    ap.add_argument("--metrics", nargs="*", default=[],
                    help="saved /metrics exposition file(s)")
    ap.add_argument("--trace", default=None,
                    help="merged cluster Perfetto trace json")
    args = ap.parse_args(argv)
    if not args.metrics and args.trace is None:
        ap.print_help()
        return 1
    out, rc = [], 0
    for p in args.metrics:
        rc |= report_metrics(p, out)
    if args.trace is not None:
        rc |= report_trace(args.trace, out)
    print("\n".join(out))
    return rc


if __name__ == "__main__":
    sys.path.insert(0, REPO_ROOT)
    sys.exit(main())
