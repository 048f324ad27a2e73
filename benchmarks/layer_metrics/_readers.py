"""Shared arithmetic of the per-layer readers.  A reader is
``read(run) -> float | None``: ``run`` holds the job's observations over the
window (``window_s``, ``stats`` deltas of ``engine.stats``, ``step_s``, ...),
the reduced trace under ``trace`` (or None), ``sizes``, ``cell``, ``config``,
``peaks`` and the end-to-end ``values``.  A reader that finds nothing to
read returns None and the metric is left out of the line."""

from __future__ import annotations

import re

from benchmarks import flops, trace_reduce


def device_idle_pct(run):
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s_first"] / tr["window_s"])


def kernel_time_pct(run, pattern: str):
    """Device time of the operations named ``pattern`` over busy time."""
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * trace_reduce.seconds_matching(
        tr["op_seconds"], pattern) / tr["busy_s"]


def stat(run, key: str):
    stats = run.get("stats")
    return None if stats is None or key not in stats else float(stats[key])


def ratio_pct(run, num: str, den: str):
    a, b = stat(run, num), stat(run, den)
    return None if a is None or not b else 100.0 * a / b


def field(run, key: str):
    v = run.get(key)
    return None if v is None else float(v)


def kv_pool_peak_pct(run):
    if "pages_peak" not in run:
        return None
    return 100.0 * run["pages_peak"] / run["num_pages"]


def flash_roofline_pct(run):
    """Least time the chip could take for the flash calls in the trace
    (operations and bytes from shapes, each call against the larger of its
    two bounds) over the time they took."""
    tr, sz = run.get("trace"), run["sizes"]
    if not tr or "seq" not in run:
        return None
    mesh = run.get("mesh") or {}
    shape = dict(batch=run["batch"] // mesh.get("dp", 1),
                 heads=sz["heads"] // mesh.get("mp", 1), seq=run["seq"],
                 head_dim=sz["hidden"] // sz["heads"])
    least = took = 0.0
    for kernel in flops.FLASH_MATMULS:
        rx = re.compile(f"^{kernel} ")
        calls = sum(c for n, c in tr["op_counts"].items() if rx.search(n))
        f, b = flops.flash_call(kernel, **shape)
        least += calls * flops.roofline_seconds(f, b, run["peaks"])[0]
        took += trace_reduce.seconds_matching(
            tr["op_seconds"], f"^{kernel} ")
    return 100.0 * least / took if took > 0 else None
