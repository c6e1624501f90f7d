"""From a profiler trace (.xplane.pb) to the numbers the benchmark reports.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device is a
plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
executed operation (start and duration in ns on the trace's clock).  The
benchmark's own spans (``bench.begin_pass`` / ``bench.train`` /
``bench.end_pass``, written with ``jax.profiler.TraceAnnotation``) are on
the host plane, on the same clock.  The traced window runs from the first
benchmark span's start to the last one's end.

    busy_s         union of op intervals inside the window, mean over chips
    window_s       length of the window
    step_busy_s    chip 0's busy time inside the bench.train spans
    collective_s   chip 0's time in collective ops inside bench.train
    top_ops        [[name, seconds], ...] chip 0, by total time, at most 10
    idle_gaps      [[span covering the gap's middle, seconds], ...] chip
                   0's longest gaps, at most 10
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
NAME_CHARS = 160  # an op's name is its whole HLO line; the start says enough
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute",
    re.I)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_seconds(intervals: list) -> float:
    """Total length of the union of (start, end) intervals, in their unit."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(intervals: list, lo: float, hi: float) -> list:
    """The idle (start, end) stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(clip(intervals, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def device_planes(profile) -> list:
    planes = [p for p in profile.planes
              if re.match(r"^/device:TPU:\d+$", p.name)]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def op_events(plane) -> list:
    """(name, start_ns, end_ns) of every executed op on a device plane."""
    out = []
    for line in plane.lines:
        if line.name == OPS_LINE:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return out


def bench_spans(profile) -> list:
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(e.name[len(SPAN_PREFIX):], e.start_ns,
                     e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return sorted(out, key=lambda s: s[1])


def reduce(path: str, n_devices: int = 1) -> dict:
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    spans = bench_spans(profile)
    planes = device_planes(profile)[:n_devices]
    if not spans:
        raise ValueError(f"{path}: no {SPAN_PREFIX}* span in the trace")
    if len(planes) < n_devices:
        raise ValueError(
            f"{path}: {len(planes)} device planes, the cell has {n_devices}")
    lo, hi = spans[0][1], max(s[2] for s in spans)
    per_dev = [op_events(p) for p in planes]
    busy = [union_seconds(clip([(s, e) for _, s, e in ev], lo, hi))
            for ev in per_dev]
    if min(busy) <= 0:
        raise ValueError(f"{path}: no operation ran on a device")
    ev0 = clip_named(per_dev[0], lo, hi)
    train = [(s, e) for n, s, e in spans if n == "train"]
    step_busy = sum(
        union_seconds(clip([(s, e) for _, s, e in ev0], a, b))
        for a, b in train)
    coll = sum(
        union_seconds(clip([(s, e) for n, s, e in ev0
                            if COLLECTIVE.search(n)], a, b))
        for a, b in train)
    by_name: dict = {}
    for n, s, e in ev0:
        by_name[n] = by_name.get(n, 0.0) + (e - s)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps([(s, e) for _, s, e in ev0], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "step_busy_s": step_busy * 1e-9,
        "collective_s": coll * 1e-9,
        "n_ops": len(ev0),
        "top_ops": [[n[:NAME_CHARS], t * 1e-9] for n, t in top],
        "idle_gaps": [[covering(spans, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in idle],
    }


def clip_named(events: list, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def covering(spans: list, t: float) -> str:
    """The benchmark span that covers instant ``t`` (the shortest one, if
    spans nest), or "none"."""
    hit = [(e - s, n) for n, s, e in spans if s <= t <= e]
    return min(hit)[1] if hit else "none"


def describe(path: str) -> None:
    """Print what a trace holds, for a look by hand: planes, lines, how
    many events, the first few names."""
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    for plane in profile.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events})[:8]
            print(f"  LINE {line.name!r}: {len(events)} events, e.g. {names}")
            if events:
                print(f"       first start {events[0].start_ns:.0f} ns, "
                      f"last end "
                      f"{events[-1].start_ns + events[-1].duration_ns:.0f}")


if __name__ == "__main__":
    import json
    import sys

    describe(sys.argv[1])
    print(json.dumps(reduce(sys.argv[1], int(sys.argv[2])
                            if len(sys.argv) > 2 else 1), indent=1))
