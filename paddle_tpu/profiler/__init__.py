"""paddle.profiler over jax.profiler.

Parity: python/paddle/profiler/profiler.py (Profiler, RecordEvent, scheduler
cycles, export_chrome_tracing) backed by paddle/fluid/platform/profiler/ host
+ CUPTI tracers. TPU-native: jax.profiler writes XPlane/Perfetto traces that
TensorBoard renders (the TPU-side analog of the Chrome trace), and
RecordEvent maps to jax.profiler.TraceAnnotation: a host span in that same
trace, on the device operations' clock, and the one place spans are kept.
"""
from __future__ import annotations

import contextlib
import os
import time
from enum import Enum
from typing import Callable, Iterable, Optional

import jax

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "SummaryView", "ChromeTrace"]


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
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


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
    def handler(prof):
        pass
    handler._dir = dir_name
    return handler


class RecordEvent:
    """User scope annotation; shows up in the XLA trace timeline."""

    def __init__(self, name: str, event_type=None):
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
    def __init__(self, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False):
        self.targets = list(targets or [ProfilerTarget.CPU, ProfilerTarget.TPU])
        if isinstance(scheduler, tuple):
            start, end = scheduler
            scheduler = make_scheduler(closed=start, ready=0,
                                       record=end - start, skip_first=0)
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._dir = None
        self._active = False
        self._step_times: list[float] = []
        self._t0 = None

    def _log_dir(self):
        if self.on_trace_ready is not None and hasattr(self.on_trace_ready, "_dir"):
            return self.on_trace_ready._dir
        return os.environ.get("PADDLE_PROFILER_DIR", "/tmp/paddle_tpu_prof")

    def start(self):
        if not self.timer_only:
            try:
                jax.profiler.start_trace(self._log_dir())
                self._active = True
            except Exception:
                self._active = False
        self._t0 = time.perf_counter()

    def stop(self):
        if self._active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._active = False
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._t0 is not None:
            self._step_times.append(now - self._t0)
        self._t0 = now
        self.step_num += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        import numpy as np
        arr = np.asarray(self._step_times[-10:])
        return (f"avg step time {arr.mean()*1000:.2f} ms "
                f"(last {arr[-1]*1000:.2f} ms)")

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        print(self.step_info())

    def export(self, path, format="json"):
        """Chrome-trace export of the timer-level step timeline (the
        XPlane dump lands in the log dir at stop(); this is the
        lightweight per-step view, same event model as the serving
        telemetry export)."""
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


def load_profiler_result(filename):
    return None
