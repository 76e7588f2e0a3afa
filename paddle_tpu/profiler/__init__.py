"""paddle.profiler over jax.profiler.

Parity: python/paddle/profiler/profiler.py (Profiler, RecordEvent, scheduler
cycles, export_chrome_tracing) backed by paddle/fluid/platform/profiler/ host
+ CUPTI tracers. TPU-native: jax.profiler writes XPlane/Perfetto traces that
TensorBoard renders (the TPU-side analog of the Chrome trace), and
RecordEvent maps to jax.profiler.TraceAnnotation: a host span in that same
trace, on the device operations' clock, and the one place spans are kept.

Reading a trace back (``load_profiler_result``, ``Profiler.summary``,
``python -m paddle_tpu.profiler <dir or .xplane.pb>``): every operation of a
compiled step carries the program's stamps in its HLO ``op_name`` (the pass
``bwd`` / ``replay`` / ``opt``, the layer's path, the hand-placed scopes:
``tensor/tensor.py``, "stamps"), the device trace keeps that name as the
``tf_op`` of each event's metadata, and the reduction here sums the device's
SELF time by pass, layer, scope, ``hlo_category`` and kernel. Paddle's
profiler prints its Model, Operator and Kernel Summaries from its own
events; these are the same three tables from the device's.
"""
from __future__ import annotations

import os
import re
import time
from collections import defaultdict
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, Iterable, Optional

import jax

from . import xplane

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "ProfilerResult", "SummaryView", "ChromeTrace", "pass_of",
           "scope_of"]


class ChromeTrace:
    """Chrome-trace (chrome://tracing / Perfetto) event builder — the
    ONE event model shared by the profiler's host-span export and the
    serving telemetry export (inference/telemetry.py), so both render
    side by side with jax.profiler's XLA timeline in Perfetto.

    Phases used: "M" metadata (process/thread names), "X" complete
    events (ts + dur), "i" instants, "C" counters. Timestamps and
    durations are MICROSECONDS (the trace-event spec's unit)."""

    def __init__(self):
        self.events = []

    def process(self, pid, name):
        self.events.append({"ph": "M", "name": "process_name",
                            "pid": pid, "tid": 0,
                            "args": {"name": name}})

    def thread(self, pid, tid, name):
        self.events.append({"ph": "M", "name": "thread_name",
                            "pid": pid, "tid": tid,
                            "args": {"name": name}})

    def complete(self, name, pid, tid, ts_us, dur_us, args=None):
        ev = {"ph": "X", "name": name, "pid": pid, "tid": tid,
              "ts": round(float(ts_us), 3),
              "dur": round(max(float(dur_us), 0.0), 3)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name, pid, tid, ts_us):
        self.events.append({"ph": "i", "name": name, "pid": pid,
                            "tid": tid, "ts": round(float(ts_us), 3),
                            "s": "t"})

    def counter(self, name, pid, ts_us, values):
        self.events.append({"ph": "C", "name": name, "pid": pid,
                            "tid": 0, "ts": round(float(ts_us), 3),
                            "args": dict(values)})

    def to_dict(self):
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}

    def write(self, path):
        import json
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SummaryView(Enum):
    """The reference's views. ``Profiler.summary`` prints ``ModelView``,
    ``OperatorView`` and ``KernelView`` and raises on the others."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


_PRINTED = (SummaryView.ModelView, SummaryView.OperatorView,
            SummaryView.KernelView)
_RECORDING = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    total = closed + ready + record

    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """An ``on_trace_ready`` handler: the profiler's trace goes under
    ``dir_name`` (``jax.profiler`` writes its ``.xplane.pb`` there when a
    recording ends) and the handler puts the timer's step timeline beside it
    as Chrome-trace JSON, ``<worker_name>.steps.json``."""
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        prof.export(os.path.join(
            dir_name, f"{worker_name or 'worker'}.steps.json"))
    handler._dir = dir_name
    return handler


class RecordEvent:
    """User scope annotation; shows up in the XLA trace timeline."""

    def __init__(self, name: str):
        self.name = name
        self._ctx = None
        self.begin_ns = None
        self.end_ns = None

    def begin(self):
        self._ctx = jax.profiler.TraceAnnotation(self.name)
        self._ctx.__enter__()
        self.begin_ns = time.perf_counter_ns()

    def end(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
        self.end_ns = time.perf_counter_ns()

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *a):
        self.end()
        return False


class Profiler:
    """``targets``: with ``ProfilerTarget.CPU`` among them (the default) the
    trace holds the host's ``TraceAnnotation`` spans beside the device's
    operations, without it the device's alone. ``scheduler``: a function of
    the step number (``make_scheduler``) or a ``(start, end)`` pair of
    steps; a trace is taken over each run of recording steps and
    ``on_trace_ready(profiler)`` is called as each ends. ``timer_only``:
    no trace, only the step timer. The trace is taken with the Python tracer
    off: it stamps every Python call and slows the host it observes."""

    def __init__(self, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False):
        self.targets = list(targets or [ProfilerTarget.CPU, ProfilerTarget.TPU])
        if isinstance(scheduler, tuple):
            start, end = scheduler
            scheduler = make_scheduler(closed=start, ready=0,
                                       record=end - start, repeat=1)
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._active = False        # a jax.profiler session is open
        self._taken = False         # a trace was written under _log_dir()
        self._step_times: list[float] = []
        self._samples = 0
        self._t0 = None

    def _log_dir(self):
        if self.on_trace_ready is not None and hasattr(self.on_trace_ready, "_dir"):
            return self.on_trace_ready._dir
        return os.environ.get("PADDLE_PROFILER_DIR", "/tmp/paddle_tpu_prof")

    def _enter(self, state):
        """Move to ``state``, opening or ending the trace as it asks."""
        if state in _RECORDING and not self._active and not self.timer_only:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            if ProfilerTarget.CPU not in self.targets:
                opts.host_tracer_level = 0
            jax.profiler.start_trace(self._log_dir(), profiler_options=opts)
            self._active = True
        elif state not in _RECORDING and self._active:
            self._finish()
        self.state = state

    def _finish(self):
        if self._active:
            self._active = False
            jax.profiler.stop_trace()
            self._taken = True
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def start(self):
        self._enter(self.scheduler(self.step_num) if self.scheduler
                    else ProfilerState.RECORD)
        self._t0 = time.perf_counter()

    def stop(self):
        if self._active or self.timer_only:
            self._finish()
        self.state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._t0 is not None:
            self._step_times.append(now - self._t0)
            self._samples += num_samples or 0
        self._t0 = now
        self.step_num += 1
        if self.scheduler:
            if self.state is ProfilerState.RECORD_AND_RETURN and self._active:
                self._finish()
            self._enter(self.scheduler(self.step_num))

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        last = self._step_times[-10:]
        out = (f"avg step time {1e3 * sum(last) / len(last):.2f} ms "
               f"(last {1e3 * last[-1]:.2f} ms)")
        if self._samples:
            out += (f", {self._samples / sum(self._step_times):.1f} "
                    f"{unit or 'samples'}/s")
        return out

    def summary(self, views=None, top=30):
        """Print the step timer's line and, from the trace that was taken,
        the views asked for (``SummaryView`` values; default: the three that
        exist). ``NotImplementedError`` for a view that does not exist here,
        ``RuntimeError`` where a trace was asked for and none was taken."""
        views = _check_views(views)
        print(self.step_info())
        if self.timer_only:
            return
        if not self._taken:
            raise RuntimeError(
                "Profiler.summary: no trace was taken (start() and stop() "
                "around at least one recording step come first)")
        print(load_profiler_result(self._log_dir()).summary(views, top))

    def export(self, path, format="json"):
        """Chrome-trace export of the timer-level step timeline (the
        XPlane dump lands in the log dir at stop(); this is the
        lightweight per-step view, same event model as the serving
        telemetry export)."""
        if format != "json":
            raise ValueError(f"Profiler.export: format {format!r}; the step "
                             "timeline is written as Chrome-trace json")
        tr = ChromeTrace()
        tr.process(0, "paddle_tpu Profiler")
        tr.thread(0, 0, "train steps")
        t = 0.0
        for i, dt in enumerate(self._step_times):
            tr.complete(f"step {i}", 0, 0, t * 1e6, dt * 1e6)
            t += dt
        tr.write(path)
        return path

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()
        return False


def _check_views(views):
    if views is None:
        return list(_PRINTED)
    views = [views] if isinstance(views, SummaryView) else list(views)
    missing = [v.name for v in views if v not in _PRINTED]
    if missing:
        raise NotImplementedError(
            f"paddle.profiler prints {[v.name for v in _PRINTED]}; not "
            f"implemented: {missing}")
    return views


# ------------------------------------------------------ reading a trace back
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, STEPS_LINE = "XLA Ops", "Steps"
PASSES = ("fwd", "replay", "bwd", "opt")

# JAX's own components of an op_name: ``jit(pure)`` and every other jitted
# function's name, the transforms that wrap the scope after them
# (``transpose(jvp(gdn.conv))``), the structure of control flow
_JIT = re.compile(r"jit\([^()]*\)")
_WRAP = re.compile(r"[A-Za-z_][\w.]*\(")
_STRUCTURE = re.compile(
    r"^(?:while|body|cond|closed_call|checkpoint|rematted_computation|scan|"
    r"remat|custom_jvp_call|custom_vjp_call|custom_vjp_call_jaxpr|"
    r"branch_\d+_fun|pallas_call|shard_map)$")
_MARKERS = {"bwd", "replay", "opt"}
_HAS = {m: re.compile(rf"(?:^|[/(]){m}(?:[/)]|$)") for m in _MARKERS}
_KERNEL = re.compile(r"^%?(?P<name>[\w\-]+?)(?:\.\d+)? = ")
_OPERAND = re.compile(r"%([\w.\-]+)")


def pass_of(op_name: str) -> str:
    """Which pass of the step an operation belongs to, from its HLO
    ``op_name``. THE rule of precedence among the program's markers
    (``tensor/tensor.py``, "stamps"): a path with ``opt`` is the
    optimizer's; else one with ``transpose(`` is backward (the replayed
    layers' backward included); else one with ``replay`` is the replay; else
    one with ``bwd`` is backward (the walk's own sums, a PyLayer's
    backward); else it is the first forward."""
    if _HAS["opt"].search(op_name):
        return "opt"
    if "transpose(" in op_name:
        return "bwd"
    if _HAS["replay"].search(op_name):
        return "replay"
    if _HAS["bwd"].search(op_name):
        return "bwd"
    return "fwd"


def scope_of(op_name: str):
    """``(layer index or None, scope path)`` of an HLO ``op_name``: the
    layers' path and the hand-placed scopes, with JAX's own components, the
    pass markers and the primitive's name (the last component) taken out, a
    path that JAX repeats collapsed to one (a jitted callee inlined by XLA
    carries its caller's path again behind a ``jit(...)``; a ``custom_vjp``'s
    backward carries the forward's behind ``transpose(``), the first numeric
    component (a layer's index in a ``LayerList`` or ``Sequential``) split
    off and every numeric component replaced by ``*``, so that the layers of
    a stack sum into one row. An operation inside a ``while`` body gets
    ``/while`` at the end."""
    path = op_name.rpartition("/")[0]
    path = _WRAP.sub("\0", _JIT.sub("\0", path)).replace(")", "")
    names, starts, edge, loop = [], [], False, False
    for part in path.split("/"):
        if "\0" in part:
            edge, part = True, part.replace("\0", "")
        if not part or part in _MARKERS:
            continue
        if _STRUCTURE.match(part) or "->" in part:  # control flow, einsum
            loop |= part == "while"
            edge = True
            continue
        names.append(part)
        starts.append(edge)
        edge = False
    out, i = [], 0
    while i < len(names):
        if starts[i] and out and names[i:i + len(out)] == out:
            i += len(out)
            continue
        out.append(names[i])
        i += 1
    layer = next((int(n) for n in out if n.isdigit()), None)
    scope = "/".join("*" if n.isdigit() else n for n in out)
    return layer, scope + "/while" if loop and scope else scope


def _self_times(events):
    """``[(metadata id, self ps)]`` of one line's ``(id, offset, duration)``
    events in the order they start, each with its nested children taken out
    of it: a ``while`` is left with what its body does not cover."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [dur for _, _, dur in order]
    stack = []                          # indices of the events still open
    for i, (_, start, dur) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(dur, order[p][1] + order[p][2] - start)
        stack.append(i)
    return [(e[0], ps) for e, ps in zip(order, own)]


@dataclass
class ProfilerResult:
    """A device trace reduced by the program's stamps. ``rows``: one dict a
    ``(pass, layer, scope, category, kernel)``, PER TRACED STEP and device:
    ``seconds`` of device self time, XLA's own ``flops`` and ``bytes`` of
    the operations, ``events`` (how many ran), ``unnamed`` (the part of
    ``seconds`` that operations without a name of their own brought:
    ``load_profiler_result``). ``steps``: traced steps (the events of the
    device's "Steps" line); ``busy_s``: the device's busy seconds a step,
    which the rows' seconds sum to; ``unscoped``: the largest operations left
    without a layer or scope, ``(seconds a step, operation, source line)``."""
    rows: list = field(default_factory=list)
    steps: int = 1
    devices: int = 0
    busy_s: float = 0.0
    path: str = ""
    unscoped: list = field(default_factory=list)

    def by(self, *keys):
        """The rows summed over everything but ``keys``: ``{key or tuple of
        keys: {"seconds", "flops", "bytes", "events", "unnamed"}}``, largest
        first."""
        sums = ("seconds", "flops", "bytes", "events", "unnamed")
        out = defaultdict(lambda: dict.fromkeys(sums, 0.0))
        for r in self.rows:
            k = r[keys[0]] if len(keys) == 1 else tuple(r[k] for k in keys)
            for f in sums:
                out[k][f] += r[f]
        return dict(sorted(out.items(), key=lambda kv: -kv[1]["seconds"]))

    def summary(self, views=None, top=30) -> str:
        """The views as text (``Profiler.summary`` prints this)."""
        views = _check_views(views)
        out = [f"{self.path}: {self.devices} device(s), {self.steps} traced "
               f"step(s), busy {1e3 * self.busy_s:.3f} ms a step"]
        if not self.rows:
            out.append("no device operation in the trace (a trace taken on "
                       "the CPU has no /device:TPU plane)")
            return "\n".join(out)
        for view in views:
            out.append(getattr(self, "_" + view.name)(top))
        return "\n".join(out)

    def _share(self, seconds):
        return 100.0 * seconds / self.busy_s if self.busy_s else 0.0

    def _ModelView(self, top):
        tot = self.by("pass")
        out = ["", "ModelView: device self time by pass of the step "
               "(unnamed: of it, operations XLA named itself, counted with an "
               "operand's maker or the operation before them)",
               f"{'pass':<8}{'ms/step':>12}{'% busy':>9}{'events':>10}"
               f"{'unnamed ms':>12}"]
        for name in PASSES:
            t = tot.get(name)
            if t:
                out.append(f"{name:<8}{1e3 * t['seconds']:>12.3f}"
                           f"{self._share(t['seconds']):>9.2f}"
                           f"{t['events']:>10.0f}"
                           f"{1e3 * t['unnamed']:>12.3f}")
        return "\n".join(out)

    def _OperatorView(self, top):
        tot = self.by("scope")
        split = self.by("scope", "pass")
        width = min(72, max([len(s) for s in tot] + [5]))
        head = "".join(f"{p:>9}" for p in PASSES)
        out = ["", "OperatorView: device self time by scope, layers summed "
               "(ms a step; TFLOP/s and GB/s from XLA's own counts)",
               f"{'scope':<{width}}{'ms/step':>10}{'% busy':>8}{head}"
               f"{'TFLOP/s':>9}{'GB/s':>8}"]
        for scope, t in list(tot.items())[:top]:
            cells = "".join(
                f"{1e3 * split.get((scope, p), {}).get('seconds', 0.0):>9.2f}"
                for p in PASSES)
            s = t["seconds"] or float("inf")
            out.append(f"{(scope or '(no layer or scope)')[-width:]:<{width}}"
                       f"{1e3 * t['seconds']:>10.3f}"
                       f"{self._share(t['seconds']):>8.2f}{cells}"
                       f"{t['flops'] / s / 1e12:>9.2f}"
                       f"{t['bytes'] / s / 1e9:>8.1f}")
        if len(tot) > top:
            rest = sum(t["seconds"] for t in list(tot.values())[top:])
            out.append(f"{f'... {len(tot) - top} more':<{width}}"
                       f"{1e3 * rest:>10.3f}{self._share(rest):>8.2f}")
        if self.unscoped:
            out.append("no layer or scope, the largest by source line:")
            out += [f"  {1e3 * sec:>9.3f} ms  {name[:40]:<40}  {src}"
                    for sec, name, src in self.unscoped[:8]]
        return "\n".join(out)

    def _KernelView(self, top):
        tot = {k: t for k, t in self.by("kernel", "pass").items() if k[0]}
        out = ["", "KernelView: Mosaic kernels (pallas_call names, and "
               "XLA's own) by pass",
               f"{'kernel':<44}{'pass':<8}{'calls/step':>11}{'ms/step':>10}"
               f"{'ms/call':>9}{'% busy':>8}"]
        for (name, pas), t in list(tot.items())[:top]:
            out.append(f"{name[-44:]:<44}{pas:<8}{t['events']:>11.1f}"
                       f"{1e3 * t['seconds']:>10.3f}"
                       f"{1e3 * t['seconds'] / max(t['events'], 1e-9):>9.3f}"
                       f"{self._share(t['seconds']):>8.2f}")
        if not tot:
            out.append("(no tpu_custom_call event in the trace)")
        return "\n".join(out)


def load_profiler_result(path) -> ProfilerResult:
    """The device trace under ``path`` (a directory ``jax.profiler`` wrote
    to, e.g. ``benchmark/run.py --keep <dir>``'s, or one ``.xplane.pb``)
    reduced by the program's stamps: a ``ProfilerResult``.

    Every event of the "XLA Ops" line of every ``/device:TPU:n`` plane gives
    its SELF time (its nested children taken out) to the row of its pass
    (``pass_of``), layer and scope (``scope_of``), ``hlo_category`` and, for a
    Mosaic kernel (``tpu_custom_call``), the kernel's name; all read from the
    ``tf_op`` and ``hlo_category`` of the event's METADATA, with XLA's
    ``flops`` and ``bytes_accessed`` an occurrence. A FUSION IS ATTRIBUTED TO
    ITS ROOT: XLA gives a fused operation the ``op_name`` of the fusion's
    root instruction, so a row holds what was fused into its operations from
    their neighbours. An event WITHOUT a path of JAX's in its ``tf_op`` (a
    layout copy, the end of an asynchronous copy, XLA's own grouped product
    ``ragged-dot-none``: operations XLA made or renamed by itself) is
    counted, under its own category, with the operation that made one of its
    operands (the instruction's text names them): the one of the latest
    pass, since whatever reads a backward value is backward; where no
    operand's maker is an event with a stamp (a parameter, a ``bitcast``),
    with the stamped operation that ran before it on the line. Its seconds
    are also in the row's ``unnamed``. Seconds are a traced step and device:
    sums over the trace divided by the events of the "Steps" line and by the
    planes."""
    file = xplane.find_xplane(path)
    space = xplane.read_xspace(file, want=DEVICE_PLANE.match)
    planes = [p for p in space["planes"] if DEVICE_PLANE.match(p["name"])]
    rows = defaultdict(lambda: [0, 0.0, 0.0, 0, 0])
    loose = defaultdict(int)
    steps, busy_ps = 1, 0
    for plane in planes:
        meta = plane["event_metadata"]
        seen = {mid: _read_metadata(md) for mid, md in meta.items()}
        makers = {md["display_name"]: mid for mid, md in meta.items()}
        adopted = {}

        def adopt(mid, depth=0):
            """The stamp of the latest pass among the makers of ``mid``'s
            operands, through makers that have none of their own."""
            if mid in adopted or depth > 4:
                return adopted.get(mid)
            found = []
            for name in _OPERAND.findall(meta[mid]["name"])[1:]:
                maker = makers.get(name)
                if maker is not None and maker != mid:
                    found.append(seen[maker][0] or adopt(maker, depth + 1))
            found = [w for w in found if w is not None]
            adopted[mid] = max(found, key=lambda w: PASSES.index(w[0]),
                               default=None)
            return adopted[mid]

        for line in plane["lines"]:
            if line["name"] == STEPS_LINE and line["events"]:
                steps = len(line["events"])
            if line["name"] != OPS_LINE:
                continue
            before = ("fwd", None, "")
            for mid, ps in _self_times(line["events"]):
                where, category, kernel, flops, nbytes, label = seen.get(
                    mid) or _read_metadata(None)
                if where is not None:
                    before = at = where
                else:
                    at = (adopt(mid) if mid in meta else None) or before
                row = rows[at + (category, kernel)]
                row[0] += ps
                row[1] += flops
                row[2] += nbytes
                row[3] += 1
                row[4] += ps if where is None else 0
                busy_ps += ps
                if not at[2]:
                    loose[label] += ps
    per = steps * max(len(planes), 1)
    out = [dict(zip(("pass", "layer", "scope", "category", "kernel"), key),
                seconds=ps / 1e12 / per, flops=fl / per, bytes=by / per,
                events=n / per, unnamed=un / 1e12 / per)
           for key, (ps, fl, by, n, un) in rows.items()]
    out.sort(key=lambda r: -r["seconds"])
    unscoped = sorted(((ps / 1e12 / per,) + label
                       for label, ps in loose.items()), reverse=True)[:20]
    return ProfilerResult(rows=out, steps=steps, devices=len(planes),
                          busy_s=busy_ps / 1e12 / per, path=file,
                          unscoped=unscoped)


def _read_metadata(md):
    """``((pass, layer, scope) or None, category, kernel, flops, bytes,
    (operation, source line))`` of an event's metadata; None where its
    ``tf_op`` is missing or is no path of JAX's (XLA's own name for an
    operation it made: ``ragged-dot-none``, ``donated_arrays[28]``)."""
    if md is None:
        return None, "", "", 0.0, 0.0, ("", "")
    stats = md["stats"]
    # "<op_name>:<op_type>", the type usually empty
    op_name = str(stats.get("tf_op") or "").rpartition(":")[0]
    kernel = ""
    if "tpu_custom_call" in md["name"]:
        m = _KERNEL.match(md["name"])
        kernel = m["name"] if m else md["display_name"]
    # a name stack is a path: `jit(pure)/...`
    return ((pass_of(op_name),) + scope_of(op_name) if "/" in op_name else None,
            str(stats.get("hlo_category") or ""), kernel,
            float(stats.get("flops") or 0),
            float(stats.get("bytes_accessed") or 0),
            (md["display_name"] or md["name"],
             str(stats.get("source") or "")))


def main(argv=None):
    """``python -m paddle_tpu.profiler <dir or .xplane.pb>``: the three
    views of a kept trace."""
    import argparse
    ap = argparse.ArgumentParser(prog="python -m paddle_tpu.profiler",
                                 description=main.__doc__)
    ap.add_argument("path", help="a trace directory (benchmark/run.py "
                    "--keep <dir>) or one .xplane.pb")
    ap.add_argument("--top", type=int, default=30,
                    help="rows of the operator and kernel views")
    args = ap.parse_args(argv)
    print(load_profiler_result(args.path).summary(top=args.top))
