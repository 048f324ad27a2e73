"""Profiler: host event tables + XLA device traces.

Role parity: ``/root/reference/paddle/fluid/platform/profiler.h:204-216``
(``RecordEvent``/``PushEvent``/``EnableProfiler``) and the Python surface
``/root/reference/python/paddle/fluid/profiler.py:314`` (``with
profiler.profiler(state, sorted_key, profile_path)``), whose report is an
op-level Calls/Total/Min/Max/Ave table.  The reference's device side
(CUPTI ``DeviceTracer``, ``device_tracer.h:43``) maps to ``jax.profiler``
TensorBoard traces: XLA records per-HLO device timelines natively, so kernel
attribution comes from the trace viewer, not hand-rolled callbacks.

Host events: :class:`RecordEvent` spans are collected into a process-global
table and (while a device trace is live) forwarded as
``jax.profiler.TraceAnnotation`` so they appear on the trace timeline.  The
eager tracer auto-wraps every op when profiling is on; with
``FLAGS_benchmark`` it also blocks per op so host spans are real kernel
times rather than async dispatch times.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Dict, List, Optional

import jax

from .framework import flags as _flags

_state = {
    "enabled": False,
    "trace": False,       # a jax.profiler trace is live
    "logdir": None,
    "events": {},         # name -> [calls, total, min, max]
    "order": [],          # first-end-time ordering (reference default sort)
}

def is_profiling() -> bool:
    return _state["enabled"]


class RecordEvent:
    """RAII host-event span (ref profiler.h:204 ``RecordEvent``).

    Usable as a context manager or via push/pop free functions.  Inside a
    live device trace the span is mirrored as a TraceAnnotation so it shows
    up in the TensorBoard trace viewer.
    """

    def __init__(self, name: str):
        self.name = name
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        if _state["trace"]:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        dt = (t1 - self._t0) * 1e3  # ms
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if _state["enabled"]:
            ev = _state["events"].get(self.name)
            if ev is None:
                _state["events"][self.name] = [1, dt, dt, dt]
                _state["order"].append(self.name)
            else:
                ev[0] += 1
                ev[1] += dt
                ev[2] = min(ev[2], dt)
                ev[3] = max(ev[3], dt)
        return False


@contextlib.contextmanager
def record_event(name: str):
    with RecordEvent(name):
        yield


def reset_profiler() -> None:
    """Clear collected host events (ref profiler.py ``reset_profiler``)."""
    _state["events"] = {}
    _state["order"] = []


def start_profiler(state: str = "All", tracer_option: str = "Default",
                   logdir: Optional[str] = None) -> None:
    """Begin collection.  ``state``: 'CPU' = host events only; 'GPU'/'TPU'/
    'All' = host events + XLA device trace (TensorBoard format)."""
    if state not in ("CPU", "GPU", "TPU", "All"):
        raise ValueError(
            "state should be 'CPU', 'GPU', 'TPU' or 'All', got %r" % (state,))
    if _state["enabled"]:
        return
    reset_profiler()
    _state["enabled"] = True
    if state != "CPU":
        _state["logdir"] = logdir or _flags.flag("FLAGS_profiler_logdir")
        try:
            jax.profiler.start_trace(_state["logdir"])
            _state["trace"] = True
        except BaseException:  # trace backend unavailable: host events only
            _state["trace"] = False


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: str = "/tmp/profile") -> None:
    """End collection: stop the device trace, print the host event table,
    dump it as JSON to ``profile_path``."""
    if sorted_key not in _SORTERS:  # validate BEFORE tearing down state
        raise ValueError("sorted_key should be None, 'calls', 'total', "
                         "'max', 'min' or 'ave', got %r" % (sorted_key,))
    if not _state["enabled"]:
        return
    _state["enabled"] = False
    if _state["trace"]:
        jax.profiler.stop_trace()
        _state["trace"] = False
    try:
        _print_report(sorted_key, profile_path)
    finally:
        # a later CPU-only session must not report this session's device trace
        _state["logdir"] = None


_SORTERS = {
    None: None,
    "default": None,
    "calls": lambda kv: -kv[1][0],
    "total": lambda kv: -kv[1][1],
    "min": lambda kv: -kv[1][2],
    "max": lambda kv: -kv[1][3],
    "ave": lambda kv: -(kv[1][1] / kv[1][0]),
}


def _print_report(sorted_key, profile_path) -> None:
    if sorted_key not in _SORTERS:
        raise ValueError("sorted_key should be None, 'calls', 'total', "
                         "'max', 'min' or 'ave', got %r" % (sorted_key,))
    events = _state["events"]
    rows = [(n, events[n]) for n in _state["order"]]
    keyf = _SORTERS[sorted_key]
    if keyf is not None:
        rows.sort(key=keyf)
    grand = sum(ev[1] for _, ev in rows) or 1.0
    print("------------------------->     Profiling Report     "
          "<-------------------------")
    print(f"Place: {jax.default_backend().upper()}\nTime unit: ms")
    print(f"{'Event':<32}{'Calls':<10}{'Total':<12}{'Min.':<12}"
          f"{'Max.':<12}{'Ave.':<12}{'Ratio.':<10}")
    payload: Dict[str, Dict[str, float]] = {}
    for name, (calls, total, mn, mx) in rows:
        ave = total / calls
        print(f"{name:<32}{calls:<10}{total:<12.5g}{mn:<12.5g}"
              f"{mx:<12.5g}{ave:<12.5g}{total / grand:<10.5g}")
    if _state["logdir"]:
        print(f"Device trace: {_state['logdir']} "
              f"(tensorboard --logdir {_state['logdir']})")
    for name, (calls, total, mn, mx) in rows:
        payload[name] = {"calls": calls, "total_ms": total, "min_ms": mn,
                         "max_ms": mx, "ave_ms": total / calls}
    try:
        with open(profile_path, "w") as f:
            json.dump(payload, f, indent=1)
    except OSError:
        pass


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: str = "/tmp/profile",
             tracer_option: str = "Default"):
    """``with profiler.profiler('All', 'total'):`` — fluid-style context."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# jax-style convenience: annotate a step range in the device trace
StepTraceAnnotation = jax.profiler.StepTraceAnnotation
TraceAnnotation = jax.profiler.TraceAnnotation


def start(logdir: Optional[str] = None) -> None:
    """2.x-style alias of start_profiler('All')."""
    start_profiler("All", logdir=logdir)


def stop(sorted_key: Optional[str] = None,
         profile_path: str = "/tmp/profile") -> None:
    stop_profiler(sorted_key, profile_path)


# ---------------------------------------------------------------------------
# utils.profiler surface (reference python/paddle/utils/profiler.py)
# ---------------------------------------------------------------------------


class ProfilerOptions:
    """Option bag for :class:`Profiler` (reference ProfilerOptions)."""

    DEFAULTS = {
        "state": "All",
        "sorted_key": "total",
        "tracer_level": "Default",
        "batch_range": [0, 100],
        "output_thread_detail": False,
        "profile_path": "none",
        "timeline_path": "none",
        "op_summary_path": "none",
    }

    def __init__(self, options=None):
        self._options = dict(self.DEFAULTS)
        if options is not None:
            self._options.update(options)

    def with_state(self, state):
        self._options["state"] = state
        return self

    def __getitem__(self, name):
        if name not in self._options:
            raise ValueError(f"ProfilerOptions does not have an option "
                             f"named {name}")
        return self._options[name]


_profiler_singleton = None


class Profiler:
    """Batch-windowed profiler driver (reference utils/profiler.Profiler):
    profiles batches inside ``batch_range`` between reset_/start_/stop."""

    def __init__(self, enabled: bool = True, options=None):
        self._enabled = enabled
        self._options = (options if isinstance(options, ProfilerOptions)
                         else ProfilerOptions(options))
        self._batch = 0
        self._running = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def start(self):
        if self._enabled and not self._running:
            lo = self._options["batch_range"][0]
            if self._batch >= lo:
                start_profiler(state=self._options["state"],
                               tracer_option=self._options["tracer_level"])
                self._running = True

    def stop(self):
        if self._running:
            path = self._options["profile_path"]
            kw = {} if path == "none" else {"profile_path": path}
            stop_profiler(sorted_key=self._options["sorted_key"], **kw)
            self._running = False

    def reset(self):
        reset_profiler()
        self._batch = 0

    def record_step(self, change_profiler_status: bool = True):
        self._batch += 1
        if not (self._enabled and change_profiler_status):
            return
        lo, hi = self._options["batch_range"]
        if self._batch == lo and not self._running:
            self.start()
        elif self._batch == hi and self._running:
            self.stop()


def get_profiler(options=None) -> Profiler:
    global _profiler_singleton
    if _profiler_singleton is None:
        _profiler_singleton = Profiler(options=options)
    return _profiler_singleton
