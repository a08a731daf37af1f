"""bench-history: read the banked perf trajectory and flag regressions.

Every bench round writes BENCH_r<NN>.json (the headline metric) and the
latest round's BENCH_DETAIL.json (per-config digests: fps, task-latency
quantiles, the health/alerts digest).  Until now that trajectory was
unread by anything — a regression was invisible until a human diffed
the files by hand.  This tool closes the loop:

    python tools/bench_history.py                      # repo-root files
    python tools/bench_history.py --dir /path --json   # machine-readable
    python tools/bench_history.py --threshold 0.10     # stricter gate
    python tools/bench_history.py --all                # every consecutive
                                                       # pair, not just the
                                                       # newest

Per metric, prints the per-round history and compares the NEWEST point
against the previous point of the same metric (source changes and
metric renames start a fresh series, so an infra swap doesn't read as
a code regression).  A drop beyond --threshold exits 1 — the CI
hook: `bench_history.py || echo PERF REGRESSION`.  Exit codes: 0 ok,
1 regression, 2 no bench files found.
"""

import argparse
import glob
import json
import os
import re
import sys

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")

def load_rounds(bench_dir):
    """[(round, parsed-dict)] sorted by round, skipping unreadable or
    metric-less files (a failed round writes rc!=0 and no `parsed`)."""
    out = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r*.json")):
        m = _ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed \
                and "value" in parsed:
            out.append((int(m.group(1)), parsed))
    out.sort(key=lambda t: t[0])
    return out


def series_by_metric(rounds):
    """{metric: [(round, value, source)]} preserving round order."""
    by = {}
    for rnd, p in rounds:
        by.setdefault(p["metric"], []).append(
            (rnd, float(p["value"]), p.get("source", "")))
    return by


def find_regressions(by_metric, threshold, check_all=False):
    """[(metric, prev_round, prev, cur_round, cur, drop_frac)] for
    same-source consecutive drops beyond `threshold`.  Default checks
    only the newest pair per metric (the CI question is "did the last
    round regress", not "did history ever dip"); --all audits every
    consecutive pair."""
    regs = []
    for metric, pts in by_metric.items():
        pairs = zip(pts, pts[1:]) if check_all \
            else (zip(pts[-2:], pts[-1:]) if len(pts) >= 2 else ())
        for (r0, v0, s0), (r1, v1, s1) in pairs:
            if s0 != s1:
                # a source change resets the baseline: not a code
                # regression
                continue
            if v0 > 0 and (v0 - v1) / v0 > threshold:
                regs.append((metric, r0, v0, r1, v1, (v0 - v1) / v0))
    return regs


def detail_digest(bench_dir):
    """The latest round's BENCH_DETAIL.json, reduced to the lines a
    trajectory reader wants: per-config fps, task-latency quantiles,
    the health/alerts digest, the per-op efficiency table and the
    stable baseline metrics.  {} when the file is absent."""
    path = os.path.join(bench_dir, "BENCH_DETAIL.json")
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            detail = json.load(f)
    except (OSError, ValueError):
        return {}
    out = {"fps_by_config": {}, "task_latency": {}, "health": {},
           "op_efficiency": {}, "frame_cache": {}, "remediation": {},
           "failover": {}, "baseline_metrics": {}}
    for d in detail:
        if not isinstance(d, dict):
            continue
        if "fps" in d:
            out["fps_by_config"][str(d.get("config"))] = d["fps"]
        elif d.get("config") == "task_latency":
            out["task_latency"] = {k: v for k, v in d.items()
                                   if k != "config"}
        elif d.get("config") == "health":
            out["health"] = {k: v for k, v in d.items()
                            if k not in ("config", "rpc_latency")}
        elif d.get("config") == "op_efficiency":
            out["op_efficiency"][d["config"]] = {
                k: v for k, v in d.items() if k != "config"}
        elif d.get("config") == "frame_cache":
            out["frame_cache"][d["config"]] = {
                k: v for k, v in d.items() if k != "config"}
        elif d.get("config") == "remediation":
            out["remediation"] = {k: v for k, v in d.items()
                                  if k != "config"}
        elif d.get("config") == "failover":
            out["failover"] = {k: v for k, v in d.items()
                               if k != "config"}
        elif d.get("config") == "baseline_metrics":
            out["baseline_metrics"] = d.get("metrics") or {}
    return out


# stable per-direction baseline gate: bench.py banks `baseline_metrics`
# (each with a declared better= direction) into BENCH_DETAIL.json;
# --write-baselines snapshots them here, and every later run compares
# against the snapshot so the serving/cache/kernel directions gate the
# moment their first healthy round banks a baseline.
BASELINES_FILE = "BENCH_BASELINES.json"


def load_baselines(bench_dir):
    path = os.path.join(bench_dir, BASELINES_FILE)
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            doc = json.load(f)
        return doc.get("metrics", {}) if isinstance(doc, dict) else {}
    except (OSError, ValueError):
        return {}


def write_baselines(bench_dir, metrics):
    path = os.path.join(bench_dir, BASELINES_FILE)
    known = {k: v for k, v in metrics.items()
             if isinstance(v, dict) and v.get("value") is not None}
    with open(path, "w") as f:
        json.dump({"metrics": known}, f, indent=1)
    return path


def find_detail_regressions(baselines, current, threshold):
    """[(metric, baseline, now, change_frac)] where a baseline-metrics
    value moved against its declared direction beyond `threshold`.
    Metrics absent from either side (no baseline banked yet, or not
    measurable this round) are skipped."""
    regs = []
    for name, base in baselines.items():
        cur = current.get(name)
        if not isinstance(base, dict) or not isinstance(cur, dict):
            continue
        b, c = base.get("value"), cur.get("value")
        if b is None or c is None or not b:
            continue
        better = base.get("better", cur.get("better", "higher"))
        change = (c - b) / abs(b)
        if better == "lower":
            change = -change
        # change is now "improvement fraction": negative = worse
        if change < -threshold:
            regs.append((name, b, c, change))
    return regs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="print the BENCH_r*.json perf trajectory and flag "
                    "regressions (exit 1)")
    ap.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="fractional drop that counts as a regression "
                         "(default %(default)s)")
    ap.add_argument("--all", action="store_true",
                    help="check every consecutive same-source pair, "
                         "not just the newest")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--write-baselines", action="store_true",
                    help="snapshot the latest BENCH_DETAIL "
                         "baseline_metrics into BENCH_BASELINES.json — "
                         "the per-direction gate (task-latency p99, "
                         "per-op efficiency, compile-cache hit rate) "
                         "compares every later run against it")
    args = ap.parse_args(argv)

    rounds = load_rounds(args.dir)
    if not rounds:
        print(f"bench-history: no BENCH_r*.json under {args.dir}",
              file=sys.stderr)
        return 2
    by_metric = series_by_metric(rounds)
    regs = find_regressions(by_metric, args.threshold, args.all)
    detail = detail_digest(args.dir)
    base_metrics = detail.get("baseline_metrics") or {}
    if args.write_baselines and base_metrics:
        path = write_baselines(args.dir, base_metrics)
        print(f"bench-history: baselines written to {path}",
              file=sys.stderr)
    detail_regs = find_detail_regressions(
        load_baselines(args.dir), base_metrics, args.threshold)

    if args.json:
        print(json.dumps({
            "rounds": [r for r, _p in rounds],
            "metrics": {m: [{"round": r, "value": v, "source": s}
                            for r, v, s in pts]
                        for m, pts in by_metric.items()},
            "regressions": [
                {"metric": m, "from_round": r0, "from": v0,
                 "to_round": r1, "to": v1, "drop": round(drop, 4)}
                for m, r0, v0, r1, v1, drop in regs],
            "detail_regressions": [
                {"metric": m, "baseline": b, "value": c,
                 "change": round(ch, 4)}
                for m, b, c, ch in detail_regs],
            "threshold": args.threshold,
            "detail": detail,
        }, indent=1))
        return 1 if regs or detail_regs else 0

    print(f"bench-history: {len(rounds)} rounds "
          f"(r{rounds[0][0]:02d}..r{rounds[-1][0]:02d}), "
          f"threshold {args.threshold:.0%}")
    for metric, pts in sorted(by_metric.items()):
        print(f"\n{metric}")
        prev = None
        for rnd, v, src in pts:
            delta = ""
            if prev is not None and prev > 0:
                delta = f"  {((v - prev) / prev):+7.1%}"
            tag = f"  [{src}]" if src else ""
            print(f"  r{rnd:02d}  {v:10.2f}{delta}{tag}")
            prev = v
    if detail:
        print("\nlatest BENCH_DETAIL digest:")
        for cfg, fps in sorted(detail.get("fps_by_config", {}).items()):
            print(f"  config {cfg}: {fps} fps")
        tl = detail.get("task_latency") or {}
        if tl:
            print("  task latency: " + "  ".join(
                f"{k}={v}" for k, v in sorted(tl.items())))
        h = detail.get("health") or {}
        if h:
            trans = h.get("alert_transitions") or {}
            fired = sum(v for k, v in trans.items()
                        if k.endswith(":firing"))
            print(f"  health: {h.get('status', '?')} "
                  f"({int(fired)} alert firings during the run)")
        eff = (detail.get("op_efficiency") or {}).get("op_efficiency")
        if eff and eff.get("ops"):
            for o in eff["ops"][:8]:
                print(f"  eff {o['op']}@{o['device']} b{o['bucket']}: "
                      f"{o['efficiency']:.2%} ({o['bound']}-bound)")
            comp = eff.get("compile") or {}
            hr = comp.get("cache_hit_rate")
            print(f"  compile: {comp.get('compiles', 0)} in "
                  f"{comp.get('compile_seconds', 0)}s, cache hit rate "
                  + (f"{hr:.0%}" if hr is not None else "n/a"))
        fcd = (detail.get("frame_cache") or {}).get("frame_cache")
        if fcd and fcd.get("enabled"):
            hr = fcd.get("hit_rate")
            print(f"  frame cache: hit rate "
                  + (f"{hr:.0%}" if hr is not None else "n/a")
                  + f", decode saved {fcd.get('decode_seconds_saved')}s"
                  f", h2d saved "
                  f"{(fcd.get('h2d_bytes_saved') or 0) / 1e6:.1f} MB")
        rem = detail.get("remediation") or {}
        if rem.get("enabled"):
            n_applied = sum(
                v for k, v in (rem.get("remediations") or {}).items()
                if "applied" in k)
            print(f"  remediation: preemption recovery "
                  f"{rem.get('preemption_recovery_s')}s, "
                  f"{int(rem.get('preemptions') or 0)} preemption(s), "
                  f"strikes {int(rem.get('strike_delta') or 0)}, "
                  f"{int(n_applied)} action(s) applied")
        fo = detail.get("failover") or {}
        if fo.get("rows_ok"):
            print(f"  failover: recovery "
                  f"{fo.get('failover_recovery_s')}s, "
                  f"{int(fo.get('tasks_lost_on_recovery') or 0)} "
                  f"task(s) lost, "
                  f"{int(fo.get('journal_replayed') or 0)} journal "
                  f"record(s) replayed")
        if base_metrics:
            print("  baselines: " + "  ".join(
                f"{k}={v.get('value')}" for k, v in
                sorted(base_metrics.items())
                if isinstance(v, dict)))
    if regs or detail_regs:
        print("\nREGRESSIONS:")
        for m, r0, v0, r1, v1, drop in regs:
            print(f"  {m}: r{r0:02d} {v0:.2f} -> r{r1:02d} {v1:.2f} "
                  f"({drop:.1%} drop > {args.threshold:.0%})")
        for m, b, c, ch in detail_regs:
            print(f"  {m}: baseline {b} -> {c} "
                  f"({-ch:.1%} worse > {args.threshold:.0%})")
        return 1
    print("\nno regressions beyond threshold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
