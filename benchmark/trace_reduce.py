"""From the profiler's trace to numbers: device busy and idle time as a
UNION of intervals (a sum counts a ``while`` and its body twice), self time
per operation, the Pallas kernels' share, and the longest idle gaps, each
labelled with the host span that covers it.

The per-operation part follows ``tools/tpu_profile.py::profile_trace``
(which sums durations from the perfetto JSON and so cannot give a busy
share); this file reads the ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) and reduces a plain structure, so that the
reduction can be checked on a hand-made trace::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [(name, start_ns, dur_ns), ...]}]}]}

``python -m benchmark.trace_reduce <dir or .xplane.pb>`` describes a trace:
its planes, lines and a few events of each.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# an event of the operation line is named by its whole HLO instruction:
# "%copy.82 = bf16[36,2,128,20,64,64]{5,4,...} copy(bf16[...] %fusion.293)"
_HLO = re.compile(r"^%?(?P<op>[\w.\-]+) = (?P<shape>[a-z0-9]+\[[\d,]*\])?")


def options():
    """How the runners start the profiler: the device's operations and the
    host's ``TraceAnnotation`` spans, WITHOUT the Python tracer, which
    stamps every Python call and slows the host it is meant to observe."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(path):
    """The newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def load(path):
    """The trace at ``path`` as the plain structure above."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# --------------------------------------------------------------- intervals
def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events):
    """Nanoseconds by operation name with every event's nested children
    taken out of it (a ``while`` is left with what its body does not
    cover)."""
    out = defaultdict(int)
    stack = []                          # [(end, name, self_ns)]

    def pop():
        _, name, ns = stack.pop()
        out[name] += ns

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            pop()
        if stack:
            end, pname, pns = stack[-1]
            stack[-1] = (end, pname, pns - min(dur, end - start))
        stack.append((start + dur, name, dur))
    while stack:
        pop()
    return dict(out)


def is_pallas(name):
    """A Pallas kernel on the device's operation line: Mosaic kernels are
    custom calls whose target is ``tpu_custom_call``."""
    return "tpu_custom_call" in name


def short(name):
    """``copy.82 bf16[36,2,128,20,64,64]`` out of the whole instruction: the
    operation as the compiled program names it, and the shape it makes."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    return f"{m['op']} {m['shape']}" if m["shape"] else m["op"]


# ----------------------------------------------------------------- reduce
def reduce(trace, window_s, host_spans=()):
    """Everything the per-layer readers and the result line need from one
    traced window of ``window_s`` seconds (the host's clock, from the
    profiler's start to its stop). ``host_spans`` names the benchmark's own
    ``TraceAnnotation`` spans, which label the idle gaps they cover."""
    devs = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devs:
        return None
    busy, by_op, pallas_ns, gaps = [], defaultdict(int), 0, []
    for plane in devs:
        events = [e for ln in plane["lines"] if ln["name"] == OPS_LINE
                  for e in ln["events"]]
        merged = union([(s, s + d) for _, s, d in events])
        busy.append(sum(e - s for s, e in merged))
        for name, ns in self_times(events).items():
            by_op[short(name)] += ns
            if is_pallas(name):
                pallas_ns += ns
        gaps += [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged,
                                                           merged[1:])]
    hosts = [(n, s, s + d) for p in trace["planes"]
             if not DEVICE_PLANE.match(p["name"])
             for ln in p["lines"] for n, s, d in ln["events"]
             if n in host_spans]

    def label(start, end):
        mid = (start + end) // 2
        inside = [(e - s, n) for n, s, e in hosts if s <= mid < e]
        return min(inside)[1] if inside else "unattributed"

    busy_ns = sum(busy)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(gaps, reverse=True)[:5]
    return {
        "busy_s": busy_ns / len(devs) / 1e9,
        "window_s": float(window_s),
        "pallas_s": pallas_ns / len(devs) / 1e9,
        "device_ops": [[n, ns / len(devs) / 1e9] for n, ns in top],
        "idle_gaps": [[label(s, e), ns / 1e9] for ns, s, e in longest],
    }


def describe(trace, n_events=4):
    """A few lines per plane, for a first look at a trace by hand."""
    out = []
    for p in trace["planes"]:
        out.append(f"plane {p['name']!r}")
        for ln in p["lines"]:
            ev = ln["events"]
            out.append(f"  line {ln['name']!r}: {len(ev)} events")
            for e in sorted(ev, key=lambda e: -e[2])[:n_events]:
                out.append(f"    {e[0][:70]!r} dur {e[2]} ns")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(load(sys.argv[1])))
