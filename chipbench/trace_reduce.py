#!/usr/bin/env python3
"""From a ``jax.profiler`` trace to device busy / idle time and op times.

    JAX_PLATFORMS=cpu python3 chipbench/trace_reduce.py <trace_dir> --out f.json

``load_xplane`` reads the ``.xplane.pb`` the profiler wrote into a plain
dict (device planes only: their lines with [name, start_ns, duration_ns]
events, and the span the whole trace covers); ``reduce`` turns that dict
into numbers. The two are apart so that the reduction is checked on a
small recorded cut (``chipbench/testdata/device_trace_cut.json``, written
by ``--keep``) without a profiler.

What a v5e trace holds (looked at by hand, PR 25): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` carries one event per
executed HLO op (fusions, custom calls = the Pallas kernels, copies),
beside ``XLA Modules`` (one event per executed program) and ``Steps``;
host threads sit in ``/host:CPU``. An op's event name is its whole HLO
text (kilobytes for a custom call); ``short_name`` keeps what stands
before `` = `` (``paged_decode_attention.14``) and ``family`` drops the
numbering (``paged_decode_attention``: the Pallas kernels carry their
function's name). ``XLA Ops`` events nest (a ``while`` spans its body's
ops), so time by op is SELF time: an event's duration less what its
children cover. Busy time is the union of the ``XLA Ops`` intervals. The
traced window runs from a chip's first op to its last: the trace itself
(``span_ns``, the first to the last event of ANY plane) starts and ends
with the profiler's own start and stop, which the host's Python tracer
records and during which the device plane records nothing, so the gaps
before the first op and after the last say nothing of the serving loop.
They are reported apart (``edge_gaps``) and are no part of the window,
of busy time or of the idle gaps.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_NUMBERING = re.compile(r"(\.\d+|\.remat\d*|\.clone)+$")


def short_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")


def family(name: str) -> str:
    return _NUMBERING.sub("", name) or name


def load_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(sorted(files)[-1])
    lo, hi, planes, seen = None, None, [], []
    for plane in pd.planes:
        keep = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                s, d = float(e.start_ns), float(e.duration_ns)
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
                if keep and line.name in (OPS_LINE, MODULES_LINE):
                    events.append([short_name(e.name), s, d])
            seen.append(f"{plane.name}/{line.name}")
            if events:
                lines.append({"name": line.name, "events": events})
        if keep:
            planes.append({"name": plane.name, "lines": lines})
    return {"span_ns": [lo, hi], "planes": planes, "seen": seen}


def union_intervals(events: list) -> list:
    """Merged [start, end] intervals of [name, start, duration] events."""
    out = []
    for _name, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def self_times(events: list) -> list:
    """[name, self duration] per event: its duration less the part its
    children (events nested inside it on the same line) cover."""
    out, stack = [], []  # stack of [end, index into out]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(d, stack[-1][0] - s)
        out.append([name, d])
        stack.append([s + d, len(out) - 1])
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy seconds (union of op intervals) and the window from the
    first op to the last, both averaged over the chips; self seconds by
    op family and seconds by program; the longest idle gaps INSIDE the
    window, and the two gaps at the profile's edges apart. Gaps are
    ``unattributed``: the program writes no host span on the profiler's
    clock yet."""
    lo, hi = trace["span_ns"]
    planes = [p for p in trace["planes"]
              if any(l["name"] == OPS_LINE for l in p["lines"])]
    if not planes:
        raise ValueError(
            f"no '{OPS_LINE}' line on a device plane; the trace has: "
            f"{trace.get('seen')}")
    busy, window, lead, trail = 0.0, 0.0, 0.0, 0.0
    n_ops, ops, mods, gaps = 0, {}, {}, []
    for p in planes:
        events = next(l["events"] for l in p["lines"] if l["name"] == OPS_LINE)
        for name, d in self_times(events):
            ops[family(name)] = ops.get(family(name), 0.0) + d
        for line in p["lines"]:
            if line["name"] == MODULES_LINE:
                for name, _s, d in line["events"]:
                    name = name.split("(")[0]
                    mods[name] = mods.get(name, 0.0) + d
        n_ops += len(events)
        merged = union_intervals(events)
        busy += sum(b - a for a, b in merged)
        window += merged[-1][1] - merged[0][0]
        lead += merged[0][0] - lo
        trail += hi - merged[-1][1]
        gaps += [merged[i + 1][0] - merged[i][1]
                 for i in range(len(merged) - 1)]
    k = len(planes)
    by_time = lambda d: sorted(  # noqa: E731
        ([n, s / k / 1e9] for n, s in d.items()), key=lambda x: -x[1])
    return {
        "planes": [p["name"] for p in planes],
        "trace_s": (hi - lo) / 1e9,
        "window_s": window / k / 1e9,
        "busy_s": busy / k / 1e9,
        "n_ops": n_ops,
        "op_seconds": by_time(ops),
        "device_ops": by_time(ops)[:top],
        "programs": by_time(mods)[:top],
        "idle_gaps": [["unattributed", g / 1e9]
                      for g in sorted(gaps, reverse=True)[:top]],
        "edge_gaps": [["before_first_op", lead / k / 1e9],
                      ["after_last_op", trail / k / 1e9]],
    }


def cut(trace: dict, n: int = 300) -> dict:
    """The trace up to the end of each chip's ``n``-th op: a recorded
    trace small enough to commit. Its span keeps the trace's own start
    (the profiler's leading edge) and ends where the cut does."""
    planes, hi = [], None
    for p in trace["planes"]:
        ops = next(l["events"] for l in p["lines"] if l["name"] == OPS_LINE)
        end = max(s + d for _n, s, d in ops[:n])
        lines = []
        for line in p["lines"]:
            ev = [e for e in line["events"] if e[1] + e[2] <= end]
            lines.append({"name": line["name"], "events": ev})
        planes.append({"name": p["name"], "lines": lines})
        hi = end if hi is None else max(hi, end)
    return {"span_ns": [trace["span_ns"][0], hi], "planes": planes,
            "seen": sorted(set(trace["seen"]))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep", default=None,
                    help="directory for device_trace_cut.json (a cut small "
                    "enough to commit as test data)")
    args = ap.parse_args()
    trace = load_xplane(args.trace_dir)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(args.keep, "device_trace_cut.json"), "w") as f:
            json.dump(cut(trace), f)
    with open(args.out, "w") as f:
        json.dump(reduce(trace), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
