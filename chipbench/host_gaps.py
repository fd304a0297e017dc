#!/usr/bin/env python3
"""Idle gaps of the device by name: what the engine's loop was doing.

    JAX_PLATFORMS=cpu python3 chipbench/host_gaps.py <trace_dir> [--keep DIR]

A server running with ``--trace`` writes its loop's phase boundaries as
``jax.profiler.TraceAnnotation`` (``engine.admit``, ``engine.provision``,
``engine.dispatch``, ``engine.device``, ``engine.emit``; see
docs/tracing.md), so a profile's ``/host:CPU`` plane carries them on the
clock of the device ops. ``load_host`` reads them, ``device_gaps`` the
stretches in which no op ran on a chip, and ``attribute`` names each gap
by the annotation that covers most of it, with every annotation's share
of the gap beside the name. The phases the loop spends in an ``await``
(lag, yield, idle) carry no annotation: their share is reported as
``await``. A gap no annotation touches (a server without ``--trace``)
stays ``unattributed``.

The command prints the ten longest gaps of a kept profile (``POST
/profile?seconds=N&dir=<path>``) with their names; ``--keep`` writes
``host_trace_cut.json``, small enough to commit as test data (recorded
on the chip: ``chipbench/testdata/host_trace_cut.json``).
``trace_reduce.reduce`` still prints ``unattributed``: wiring
``attribute`` into it is the next benchmark PR's.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import trace_reduce  # noqa: E402

HOST_PLANE = "/host:CPU"
PREFIX = "engine."
#: spans the whole capture (the engine opens it right after start_trace)
CAPTURE = "engine.profile"
UNATTRIBUTED = "unattributed"
AWAIT = "await"


def load_host(trace_dir: str) -> list:
    """The ``engine.*`` events of the host plane's lines as
    ``[name, start_ns, duration_ns, stats]``, by start."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(sorted(files)[-1]).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    stats = {k: v for k, v in e.stats
                             if isinstance(v, (int, float, str))}
                    events.append([e.name, float(e.start_ns),
                                   float(e.duration_ns), stats])
    return sorted(events, key=lambda e: e[1])


def device_gaps(trace: dict) -> list:
    """``[start_ns, end_ns]`` of every stretch between a chip's first op
    and its last in which none of its ops ran (``trace`` as
    ``trace_reduce.load_xplane`` gives it), longest first."""
    gaps = []
    for p in trace["planes"]:
        for line in p["lines"]:
            if line["name"] == trace_reduce.OPS_LINE:
                merged = trace_reduce.union_intervals(line["events"])
                gaps += [[a[1], b[0]] for a, b in zip(merged, merged[1:])]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def attribute(gaps: list, host_events: list) -> list:
    """``[name, seconds, {annotation: share of the gap}]`` per gap: the
    annotation whose events cover most of it names it; one that none
    touches is ``unattributed``. The shares tell how far to trust the
    name: ``await`` is the part no annotation covers, which on a server
    with ``--trace`` the loop spent in an await (``yield``, ``lag``)."""
    events = [e for e in host_events if e[0] != CAPTURE]
    out = []
    for a, b in gaps:
        cover = {}
        for name, s, d, _stats in events:
            o = min(b, s + d) - max(a, s)
            if o > 0:
                cover[name] = cover.get(name, 0.0) + o
        shares = {n: round(c / (b - a), 4)
                  for n, c in sorted(cover.items(), key=lambda x: -x[1])}
        name = next(iter(shares), UNATTRIBUTED)
        if shares:
            shares[AWAIT] = round(max(1.0 - sum(cover.values()) / (b - a), 0.0), 4)
        out.append([name, (b - a) / 1e9, shares])
    return out


def cut(trace: dict, host_events: list, top: int = 50) -> dict:
    """The ``top`` longest gaps with where they lie and every
    ``engine.*`` host event: what ``attribute`` needs of a profile."""
    return {"span_ns": trace["span_ns"], "gaps": device_gaps(trace)[:top],
            "host": host_events}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--keep", default=None,
                    help="directory for host_trace_cut.json")
    args = ap.parse_args()
    trace = trace_reduce.load_xplane(args.trace_dir)
    host = load_host(args.trace_dir)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(args.keep, "host_trace_cut.json"), "w") as f:
            json.dump(cut(trace, host), f)
    counts = {}
    for e in host:
        counts[e[0]] = counts.get(e[0], 0) + 1
    print(f"host plane: {json.dumps(counts)}")
    gaps = device_gaps(trace)[:args.top]
    for (a, _b), (name, seconds, shares) in zip(gaps, attribute(gaps, host)):
        print(f"{seconds * 1e3:9.3f} ms at {(a - trace['span_ns'][0]) / 1e9:8.4f} s"
              f"  {name:18s} {json.dumps(shares)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
