#!/usr/bin/env python
"""Bench trend gate: compare the latest bench rows against history.

``bench.py`` appends every emitted row to ``BENCH_HISTORY.jsonl`` (one
JSON object per line, stamped with the run identity — git sha, start
time, backend, jax version, host).  This tool turns that accumulation
into a regression gate:

- rows are grouped per ``(metric, backend)`` — a CPU smoke number must
  never be judged against TPU history and vice versa;
- the baseline for a group is the MEDIAN of its historical values, and
  the noise band is ``max(rel_band * |median|, mad_k * MAD)`` — median +
  MAD because bench history contains outliers by construction (a
  throttled host, a cold page cache) and a mean/stddev gate would let a
  single bad historical run widen the band forever;
- direction comes from the metric name: throughput-shaped metrics
  (samples/sec, qps, auc, hit rate) regress DOWN, latency/size-shaped
  metrics (ms, seconds, bytes, gap) regress UP; metrics matching
  neither are reported informationally and never gate;
- rows with a null value (a stage that failed and emitted its error row
  instead of a measurement) are tolerated everywhere: they are counted
  and reported but neither form a baseline nor fail the gate.

Usage:
    python tools/bench_trend.py                      # gate last run vs prior
    python tools/bench_trend.py --current rows.jsonl # gate a file vs history
    python tools/bench_trend.py --list               # dump per-group stats
    python tools/bench_trend.py --history H.jsonl --rel-band 0.15

Exit status: 1 if any gated metric regressed outside its noise band,
0 otherwise (including "not enough history yet").
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# direction heuristics over metric names; first match wins, HIGHER first
# so e.g. "samples_per_sec" never trips the "_s" latency suffix
_HIGHER = re.compile(
    r"per_sec|per_s\b|samples|qps|auc|hit_rate|throughput|ratio_speedup")
_LOWER = re.compile(
    r"_ms\b|_ms_|ms$|_s$|seconds|latency|bytes|gap|_p99|_p50|alerts")


def default_history_path() -> str:
    env = os.environ.get("PBOX_BENCH_HISTORY")
    if env is not None:
        return env
    return os.path.join(REPO, "BENCH_HISTORY.jsonl")


def metric_direction(name: str):
    """'higher' | 'lower' | None (ungated, informational only)."""
    if _HIGHER.search(name):
        return "higher"
    if _LOWER.search(name):
        return "lower"
    return None


def load_rows(path: str) -> list:
    """Parse a JSONL file into row dicts; malformed lines are skipped
    (a truncated last line from a killed bench run must not kill the
    gate that exists to notice such runs)."""
    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if isinstance(obj, dict) and "metric" in obj:
                    rows.append(obj)
    except OSError:
        pass
    return rows


def _run_key(row: dict):
    run = row.get("run") or {}
    return (run.get("started_at"), run.get("pid"), run.get("host"))


def split_last_run(rows: list) -> tuple:
    """(history_rows, current_rows): the rows of the most recent run
    identity vs everything before it.  Rows with no run stamp (pre-stamp
    history) always count as history."""
    stamped = [r for r in rows if (r.get("run") or {}).get("started_at")]
    if not stamped:
        return rows, []
    last = max(_run_key(r) for r in stamped)
    current = [r for r in stamped if _run_key(r) == last]
    history = [r for r in rows if r not in current]
    return history, current


def _median(xs: list) -> float:
    s = sorted(xs)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def group_history(rows: list) -> dict:
    """{(metric, backend): [values]} over measured rows only — null
    values and unavailable backends never form a baseline."""
    groups: dict = {}
    for r in rows:
        v = r.get("value")
        backend = r.get("backend")
        if v is None or backend in (None, "unavailable"):
            continue
        if not isinstance(v, (int, float)):
            continue
        groups.setdefault((r["metric"], backend), []).append(float(v))
    return groups


def compare(current: list, history: list, rel_band: float = 0.10,
            mad_k: float = 3.0, min_history: int = 3) -> list:
    """One verdict dict per current row.

    status: ``regression`` (outside the band in the bad direction),
    ``ok`` (in band or improved), ``no_baseline`` (fewer than
    ``min_history`` measured rows for the group), ``ungated`` (no
    direction heuristic for the metric), ``unavailable`` (diagnostic
    row, value null).  Only ``regression`` fails the gate.
    """
    groups = group_history(history)
    out = []
    for row in current:
        metric = row.get("metric", "?")
        backend = row.get("backend")
        value = row.get("value")
        verdict = {"metric": metric, "backend": backend, "value": value}
        if value is None or backend in (None, "unavailable"):
            verdict["status"] = "unavailable"
            out.append(verdict)
            continue
        base = groups.get((metric, backend), [])
        if len(base) < min_history:
            verdict.update(status="no_baseline", n_history=len(base))
            out.append(verdict)
            continue
        med = _median(base)
        mad = _median([abs(x - med) for x in base])
        band = max(rel_band * abs(med), mad_k * mad)
        direction = metric_direction(metric)
        verdict.update(baseline=med, band=band, n_history=len(base),
                       direction=direction)
        if direction is None:
            verdict["status"] = "ungated"
        elif direction == "higher" and float(value) < med - band:
            verdict["status"] = "regression"
        elif direction == "lower" and float(value) > med + band:
            verdict["status"] = "regression"
        else:
            verdict["status"] = "ok"
        out.append(verdict)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="gate the latest bench rows against BENCH_HISTORY")
    ap.add_argument("--history", default=None,
                    help="history JSONL (default: $PBOX_BENCH_HISTORY or "
                         "BENCH_HISTORY.jsonl at the repo root)")
    ap.add_argument("--current", default=None,
                    help="JSONL of candidate rows; default: the most "
                         "recent run identity found in the history itself")
    ap.add_argument("--rel-band", type=float, default=0.10,
                    help="relative noise band floor (default 0.10)")
    ap.add_argument("--mad-k", type=float, default=3.0,
                    help="MAD multiplier for the noise band (default 3)")
    ap.add_argument("--min-history", type=int, default=3,
                    help="measured rows required before a group gates")
    ap.add_argument("--list", action="store_true",
                    help="dump per-(metric, backend) history stats, exit 0")
    args = ap.parse_args(argv)

    hist_path = args.history or default_history_path()
    rows = load_rows(hist_path)
    if not rows:
        print(f"bench-trend: no history at {hist_path} — nothing to gate")
        return 0

    if args.list:
        for (metric, backend), vals in sorted(group_history(rows).items()):
            med = _median(vals)
            mad = _median([abs(x - med) for x in vals])
            print(f"{metric:48s} {backend:12s} n={len(vals):3d} "
                  f"median={med:g} mad={mad:g} "
                  f"dir={metric_direction(metric) or 'ungated'}")
        n_un = sum(1 for r in rows if r.get("backend") == "unavailable")
        if n_un:
            print(f"({n_un} unavailable-backend diagnostic row(s) excluded)")
        return 0

    if args.current:
        history, current = rows, load_rows(args.current)
    else:
        history, current = split_last_run(rows)
    if not current:
        print("bench-trend: no current rows to judge (history has no "
              "run-stamped rows and no --current given)")
        return 0

    verdicts = compare(current, history, rel_band=args.rel_band,
                       mad_k=args.mad_k, min_history=args.min_history)
    regressed = [v for v in verdicts if v["status"] == "regression"]
    for v in verdicts:
        if v["status"] == "regression":
            worse = ("below" if v["direction"] == "higher" else "above")
            print(f"REGRESSION {v['metric']} [{v['backend']}]: "
                  f"{v['value']:g} is {worse} baseline {v['baseline']:g} "
                  f"± {v['band']:g} (n={v['n_history']})", file=sys.stderr)
        elif v["status"] == "ok":
            print(f"ok         {v['metric']} [{v['backend']}]: "
                  f"{v['value']:g} vs {v['baseline']:g} ± {v['band']:g}")
        elif v["status"] == "unavailable":
            print(f"skip       {v['metric']}: backend unavailable "
                  "(diagnostic row)")
        else:
            print(f"{v['status']:<10s} {v['metric']} [{v['backend']}]")
    if regressed:
        print(f"bench-trend: {len(regressed)} regression(s) out of "
              f"{len(verdicts)} row(s)", file=sys.stderr)
        return 1
    print(f"bench-trend: {len(verdicts)} row(s), no regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
