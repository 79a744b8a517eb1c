#!/usr/bin/env python
"""Fleet telemetry merge: N processes' metrics into ONE operator view.

The telemetry server (paddle_tpu/profiler/telemetry_server.py) exposes
one process; the JSONL sinks (tools/metrics_export.py) persist one
process; a fleet has many of both. This tool is the fleet boundary:

  * **scrape** — ``--url http://host:9100`` (repeatable) pulls
    ``/metrics.json`` + ``/goodput`` from live telemetry endpoints;
  * **sinks** — ``--sink '/shared/metrics/*.jsonl'`` (repeatable globs)
    reads the shared-directory JSONL sinks (the AOT-store-style analog:
    every host writes its own crash-safe file, any host merges them);
  * **merge** — one policy-honoring merge
    (profiler/metrics.METRIC_MERGE: sum for occurrence mass and
    fleet-additive gauges, max for watermarks, last for config values)
    PLUS a per-host-labeled exposition: every series gains a
    ``host="..."`` label so dashboards see both the fleet total and the
    straggler;
  * **fleet goodput + drift** — the fleet-truthful goodput is DERIVED
    from the summed goodput wall-time buckets (sum productive / sum
    total — exactly the hand-merge of the per-host accountant
    snapshots, pinned ±1e-9 by tests/test_telemetry_server.py), and the
    drift section names the slowest host: per-host step-time p50, the
    slowest/fastest ratio, per-host goodput and MFU, and each host's
    per-step skip/stall indices.

Usage::

    # scrape two live trainers
    python tools/fleet_metrics.py --url http://h1:9100 --url http://h2:9100

    # merge a shared sink directory into Prometheus text (host-labeled)
    python tools/fleet_metrics.py --sink '/shared/metrics/*.jsonl' --prom

    # one policy-merged exposition (no host labels), or the raw JSON view
    python tools/fleet_metrics.py --sink '...' --merged-prom
    python tools/fleet_metrics.py --url http://h1:9100 --json
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import urllib.request
from urllib.parse import urlparse

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

__all__ = ["fetch_host", "fetch_fleet", "sink_hosts", "relabel_snapshot",
           "fleet_view", "merge_goodput", "format_fleet_summary"]


def fetch_host(url, timeout=10):
    """Scrape one telemetry endpoint: (metrics snapshot, goodput
    snapshot). Raises on an unreachable host — the caller decides
    whether a partial fleet view is acceptable (the CLI warns and
    continues)."""
    base = url.rstrip("/")
    out = []
    for ep in ("/metrics.json", "/goodput"):
        with urllib.request.urlopen(base + ep, timeout=timeout) as r:
            out.append(json.loads(r.read().decode()))
    return out[0], out[1]


def fetch_fleet(url, timeout=10):
    """Scrape one host's `/fleet` elastic-fabric view
    (distributed/fabric.fleet_report): its membership generation plus —
    on the coordinator host — the whole fleet's per-host reported
    generations and `stale_hosts`. Returns None when the endpoint is
    absent (a pre-fabric server), unreachable, or unarmed; the fleet
    view then degrades to the metrics-only classification."""
    base = url.rstrip("/")
    try:
        with urllib.request.urlopen(base + "/fleet", timeout=timeout) as r:
            doc = json.loads(r.read().decode())
    except Exception:
        return None
    return doc if isinstance(doc, dict) and doc.get("armed") else None


def sink_hosts(patterns):
    """Read JSONL sinks into {host_label: (metrics, goodput)}. The host
    label is the sink row's `host:pid` when present (metrics_export
    stamps both), else the file's basename — unique per process either
    way."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import metrics_export
    hosts = {}
    paths = []
    for pat in patterns:
        hit = sorted(glob.glob(pat))
        paths.extend(hit if hit else [pat])
    for p in paths:
        rows = metrics_export.read_sink(p)
        if not rows:
            continue
        last = rows[-1]
        host = last.get("host")
        pid = last.get("pid")
        label = (f"{host}:{pid}" if host and pid
                 else os.path.splitext(os.path.basename(p))[0])
        hosts[label] = (last.get("metrics") or {},
                        last.get("goodput") or {})
    return hosts


def relabel_snapshot(snap, host):
    """Copy a registry snapshot with `host=<label>` added to every
    series — the per-host fleet exposition (distinct host labels keep
    every process's series separate through merge_snapshots)."""
    out = {}
    for name, fam in snap.items():
        series = []
        for row in fam.get("series", ()):
            row = json.loads(json.dumps(row))       # deep, JSON-typed copy
            labels = dict(row.get("labels") or {})
            labels["host"] = str(host)
            row["labels"] = labels
            series.append(row)
        out[name] = {"type": fam["type"], "help": fam.get("help", ""),
                     "labelnames": list(fam.get("labelnames", []))
                     + ["host"],
                     "series": series}
    return out


def merge_goodput(goodputs):
    """Hand-merge N accountant snapshots into the fleet-truthful view:
    wall-time buckets ADD (each host's wall clock is independent), fleet
    goodput = summed productive / summed total, throughput adds, and the
    per-step attribution indices keep their host prefix."""
    buckets = {}
    tokens_per_sec = 0.0
    steps = 0
    step_indices = {}
    for host, g in goodputs.items():
        for b, v in (g.get("buckets_s") or {}).items():
            buckets[b] = buckets.get(b, 0.0) + float(v)
        tokens_per_sec += float(g.get("tokens_per_sec") or 0.0)
        steps += int(g.get("steps") or 0)
        for b, idx in (g.get("step_indices") or {}).items():
            step_indices.setdefault(b, {})[host] = list(idx)
    total = sum(buckets.values())
    return {
        "steps": steps,
        "tokens_per_sec": round(tokens_per_sec, 2),
        "buckets_s": {b: round(v, 4) for b, v in sorted(buckets.items())},
        "goodput": (buckets.get("productive", 0.0) / total
                    if total > 0 else 0.0),
        "step_indices": step_indices,
    }


def _host_step_p50_ms(metrics, g):
    """One host's representative step-time p50 (ms): the training
    accountant's when it stepped, else the serving decode histogram."""
    p50 = float((g or {}).get("step_ms_p50") or 0.0)
    if p50 > 0:
        return p50
    from paddle_tpu.profiler.metrics import LogHistogram
    fam = (metrics or {}).get("serve_step_seconds") or {}
    for row in fam.get("series", ()):
        if row.get("count"):
            return LogHistogram.snapshot_quantile(row, 0.5) * 1e3
    return 0.0


def _fleet_generations(hosts, fleet):
    """{label: generation} + the stale label set, from per-host `/fleet`
    scrapes. Two stale signals agree by construction and are OR-ed here:
    a host's own reported generation trailing the fleet max, and the
    coordinator's `stale_hosts` list (fabric host_ids, mapped back to
    scrape labels via each member report's `host` field)."""
    generations = {}
    stale = set()
    host_id_to_label = {}
    coord_stale_ids = set()
    for label, rep in (fleet or {}).items():
        if not rep or label not in hosts:
            continue
        if rep.get("generation") is not None:
            generations[label] = int(rep["generation"])
        member = rep.get("member") or {}
        if member.get("host"):
            host_id_to_label[str(member["host"])] = label
        coord = rep.get("coordinator") or {}
        coord_stale_ids.update(str(h) for h in coord.get("stale_hosts")
                               or ())
    gmax = max(generations.values(), default=0)
    stale.update(h for h, g in generations.items() if g < gmax)
    stale.update(host_id_to_label.get(h, h) for h in coord_stale_ids)
    return generations, stale


def fleet_view(hosts, bands=None, leg=None, fleet=None):
    """{host: (metrics snapshot, goodput snapshot)} -> the full fleet
    report: policy-merged totals, host-labeled series, fleet goodput,
    and the drift section (slowest-host step-time ratio, per-host
    goodput/MFU, and — when a perf-baseline `bands` entry is given —
    per-host straggler classification against the SAME tolerance bands
    the regression sentinel enforces in-process). `fleet` optionally
    maps host labels to their `/fleet` scrapes (fetch_fleet): a host
    whose elastic-fabric generation trails the fleet's — or that the
    coordinator lists in `stale_hosts` — is classified `stale_member`
    and excluded from the drift ratio (its step times describe a mesh
    the fleet already rebuilt away from)."""
    from paddle_tpu.profiler.metrics import merge_snapshots
    merged = merge_snapshots([m for m, _ in hosts.values()])
    labeled = merge_snapshots([relabel_snapshot(m, h)
                               for h, (m, _) in hosts.items()])
    fleet_goodput = merge_goodput({h: g for h, (_, g) in hosts.items()})
    generations, stale = _fleet_generations(hosts, fleet)
    per_host = {}
    for h, (m, g) in sorted(hosts.items()):
        p50 = round(_host_step_p50_ms(m, g), 4)
        # a host that never finalized a goodput window and never served
        # is reporting, not running — it must not skew the drift stats
        active = int((g or {}).get("steps") or 0) > 0 or p50 > 0
        per_host[h] = {
            "status": ("stale_member" if h in stale
                       else "ok" if active else "no_data"),
            "goodput": (g or {}).get("goodput"),
            "mfu": (g or {}).get("mfu"),
            "tokens_per_sec": (g or {}).get("tokens_per_sec"),
            "step_p50_ms": p50,
            "step_indices": (g or {}).get("step_indices_pretty") or {},
        }
        if h in generations:
            per_host[h]["generation"] = generations[h]
    stepped = {h: v["step_p50_ms"] for h, v in per_host.items()
               if v["status"] == "ok" and v["step_p50_ms"] > 0}
    drift = {"per_host": per_host,
             "no_data_hosts": sorted(h for h, v in per_host.items()
                                     if v["status"] == "no_data")}
    if generations:
        drift["generations"] = generations
        drift["fleet_generation"] = max(generations.values())
    if stale:
        drift["stale_members"] = sorted(stale)
    # the ratio needs two measured hosts: a single host (or one measured
    # host among no_data peers) has no straggler to name, and a 1.0x
    # self-ratio would read as a finding
    if len(stepped) >= 2:
        slowest = max(stepped, key=stepped.get)
        fastest = min(stepped, key=stepped.get)
        drift.update({
            "slowest_host": slowest,
            "fastest_host": fastest,
            # the straggler statistic: >1.05 on a synchronous fleet
            # means the slow host gates every step
            "step_time_ratio": round(stepped[slowest]
                                     / stepped[fastest], 4)
            if stepped[fastest] > 0 else None,
        })
    if bands:
        drift["baseline_leg"] = leg
        drift["stragglers"] = _classify_hosts(hosts, per_host, bands)
    return {"hosts": sorted(hosts), "fleet_goodput": fleet_goodput,
            "drift": drift, "merged": merged, "labeled": labeled}


def _classify_hosts(hosts, per_host, bands):
    """Run each measured host's goodput snapshot through the sentinel's
    `classify` against a checked-in leg's bands. Only the dimensions a
    goodput snapshot carries (goodput floor, step-time bands, throughput
    floor) can fire — the event-histogram/compile bands need the
    in-process sentinel. {host: [findings]} for violating hosts only."""
    from paddle_tpu.profiler.sentinel import classify
    out = {}
    for h, (m, g) in sorted(hosts.items()):
        if per_host[h]["status"] != "ok":
            continue
        g = g or {}
        rec = {
            "leg": h, "kind": "train",
            "steps": int(g.get("steps") or 0),
            "serve_steps": 0,
            "goodput": float(g.get("goodput") or 0.0),
            "buckets_s": g.get("buckets_s") or {},
            "step_ms_p50": float(g.get("step_ms_p50") or 0.0),
            "step_ms_p99": float(g.get("step_ms_p99") or 0.0),
            "tokens_per_sec": float(g.get("tokens_per_sec") or 0.0),
            # closed-set dimensions a remote snapshot cannot see: keep
            # them band-neutral instead of trivially violating
            "reasons": {}, "compiles": {}, "hangs": 0, "skips": 0,
        }
        fs = classify(rec, bands)
        if fs:
            out[h] = fs
    return out


def format_fleet_summary(view):
    fg = view["fleet_goodput"]
    lines = ["================ fleet metrics ================",
             f"hosts   : {len(view['hosts'])} "
             f"({', '.join(view['hosts'][:8])}"
             + (" ..." if len(view["hosts"]) > 8 else "") + ")",
             f"goodput : {fg['goodput']:.4f} over {fg['steps']} step(s), "
             f"{fg['tokens_per_sec']} tok/s fleet-wide",
             f"buckets : " + " ".join(f"{b}={v}" for b, v
                                      in fg["buckets_s"].items() if v)]
    drift = view["drift"]
    if drift.get("fleet_generation") is not None:
        gens = drift.get("generations") or {}
        lines.append(
            f"fabric  : generation {drift['fleet_generation']} ("
            + ", ".join(f"{h}=g{g}" for h, g in sorted(gens.items()))
            + ")")
    if drift.get("step_time_ratio") is not None:
        lines.append(
            f"drift   : slowest {drift['slowest_host']} is "
            f"{drift['step_time_ratio']}x {drift['fastest_host']} "
            "(step-time p50 ratio)")
    if drift.get("no_data_hosts"):
        lines.append("no data : " + ", ".join(drift["no_data_hosts"])
                     + " (reporting but not running; excluded from drift)")
    if drift.get("stale_members"):
        lines.append(
            "stale   : " + ", ".join(drift["stale_members"])
            + " (heartbeating a generation the fleet rebuilt past; "
            "excluded from drift — restart or let the member rejoin)")
    for h, row in drift["per_host"].items():
        extra = ""
        idx = row.get("step_indices") or {}
        if idx:
            extra = " | " + "; ".join(f"{b} steps {s}"
                                      for b, s in sorted(idx.items()))
        if row["status"] == "no_data":
            lines.append(f"  {h:<24} no_data")
            continue
        if row["status"] == "stale_member":
            lines.append(f"  {h:<24} stale_member "
                         f"(generation {row.get('generation')})")
            continue
        lines.append(
            f"  {h:<24} goodput={row['goodput']} mfu={row['mfu']} "
            f"p50={row['step_p50_ms']}ms"
            f" tok/s={row['tokens_per_sec']}{extra}")
    for h, fs in sorted((drift.get("stragglers") or {}).items()):
        for f in fs:
            lines.append(f"  !! {h}: {f['reason']} — {f['message']} "
                         f"(leg {drift.get('baseline_leg')})")
    lines.append("===============================================")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fleet_metrics",
        description="merge N processes' telemetry (live /metrics.json "
                    "endpoints and/or shared JSONL sinks) into one "
                    "fleet view with per-host labels and a drift report")
    ap.add_argument("--url", action="append", default=[],
                    help="telemetry endpoint base URL (repeatable): "
                         "scrapes /metrics.json + /goodput")
    ap.add_argument("--sink", action="append", default=[],
                    help="JSONL sink file/glob (repeatable), as written "
                         "by tools/metrics_export.MetricsSink")
    ap.add_argument("--prom", action="store_true",
                    help="render the per-host-labeled fleet exposition")
    ap.add_argument("--merged-prom", action="store_true",
                    help="render the policy-merged exposition "
                         "(no host labels)")
    ap.add_argument("--json", action="store_true",
                    help="print the full fleet view as JSON")
    ap.add_argument("--leg", default=None,
                    help="classify every host against this perf-baseline "
                         "leg's tolerance bands (needs --baseline) — "
                         "cross-host straggler detection with the "
                         "regression sentinel's own classify()")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="with --leg: the perf baseline file "
                         "(sentinel.PerfBaseline JSON; none is shipped)")
    args = ap.parse_args(argv)
    if not args.url and not args.sink:
        ap.error("at least one --url or --sink is required")
    if args.leg and not args.baseline:
        ap.error("--leg needs --baseline FILE (no baseline is shipped)")

    bands = None
    if args.leg:
        from paddle_tpu.profiler.sentinel import PerfBaseline
        entry = PerfBaseline.load(args.baseline).match(args.leg)
        if entry is None:
            print(f"fleet_metrics: no perf-baseline entry for leg "
                  f"{args.leg!r} in {args.baseline}", file=sys.stderr)
            return 1
        bands = entry.get("bands") or {}

    from paddle_tpu.profiler.metrics import exposition

    hosts = {}
    fleet = {}
    if args.sink:
        hosts.update(sink_hosts(args.sink))
    for url in args.url:
        label = urlparse(url).netloc or url
        try:
            hosts[label] = fetch_host(url)
        except Exception as e:
            print(f"fleet_metrics: {url} unreachable ({e}); continuing "
                  "with the rest of the fleet", file=sys.stderr)
            continue
        # best-effort elastic-fabric scrape: absent/unarmed -> None, and
        # the view degrades to the metrics-only classification
        fleet[label] = fetch_fleet(url)
    if not hosts:
        print("fleet_metrics: no reachable hosts / readable sinks",
              file=sys.stderr)
        return 1
    view = fleet_view(hosts, bands=bands, leg=args.leg, fleet=fleet)
    if args.json:
        print(json.dumps(view, indent=2, sort_keys=True, default=str))
    elif args.prom:
        sys.stdout.write(exposition(view["labeled"]))
    elif args.merged_prom:
        sys.stdout.write(exposition(view["merged"]))
    else:
        print(format_fleet_summary(view))
    return 0


if __name__ == "__main__":
    sys.exit(main())
