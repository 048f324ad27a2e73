"""From a profiler trace (``.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  What it
reports, per device plane (``/device:TPU:<n>``) and averaged over them:

* ``busy_s``: the union of the intervals in which an operation ran on the
  device (line ``XLA Ops``), clipped to the traced window;
* ``op_seconds`` and ``op_counts``: device time and events by operation name, containers (an event that
  encloses later events of the same line: ``while``, ``conditional``) left
  out so that no time is counted twice;
* ``collective_s`` and ``collective_exposed_s``: time in collective
  operations (synchronous ones on ``XLA Ops``, and the span from start to
  done of asynchronous ones on ``Async XLA Ops``), and the part of it during
  which no other operation ran on that device;
* ``idle_gaps``: the idle intervals of the first device, each attributed to
  the benchmark's own ``TraceAnnotation`` span (host plane, same clock) that
  covers most of it, summed by span name.

The traced window is the host span named ``WINDOW_SPAN``; without one it is
the extent of the device events.

``python -m benchmarks.trace_reduce <file>`` prints the planes, lines and a
sample of events, for reading a trace by hand.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import sys

WINDOW_SPAN = "bench_traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"    # the spans of start/done pairs
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)")
#: idle intervals shorter than this are launch gaps between back-to-back
#: operations; they are summed under one name and not attributed
MIN_GAP_S = 20e-6
UNATTRIBUTED = "no_benchmark_span"
LAUNCH_GAPS = "gaps_under_20us"

Interval = tuple[float, float]


def newest_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def union(intervals: list[Interval]) -> list[Interval]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: list[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals: list[Interval], cover: list[Interval]
             ) -> list[Interval]:
    """The parts of ``intervals`` (disjoint, sorted) outside ``cover``
    (disjoint, sorted)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


_HLO = re.compile(r"^%?([\w.\-]+) = (.*?) ?([a-z][a-z\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTANCE = re.compile(r"\.\d+$")


def short_name(name: str) -> str:
    """A device event is named by its whole HLO instruction
    (``%flash_fwd.18 = (bf16[96,2048,128]{...}, ...) custom-call(...)``).
    Keep the instruction's name without its instance number, the result
    type without layouts and the opcode: ``flash_fwd (bf16[96,2048,128],
    f32[96,1,2048]) custom-call``.  The same operation of every layer then
    sums under one key, and a Pallas kernel is found by its ``name=`` at the
    start.  Other names (host spans) pass unchanged."""
    m = _HLO.match(name)
    if not m:
        return name
    instr, result, opcode = m.groups()
    return (f"{_INSTANCE.sub('', instr)} {_LAYOUT.sub('', result)} "
            f"{opcode}")[:160]


def _events(line) -> list[tuple[str, float, float]]:
    """(short name, start s, end s) of a line's events, by start."""
    evs = [(short_name(e.name), e.start_ns * 1e-9,
            (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]
    evs.sort(key=lambda e: (e[1], -e[2]))
    return evs


def _leaves(evs: list[tuple[str, float, float]]
            ) -> list[tuple[str, float, float]]:
    """Events that enclose no later event of the same line."""
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < ev[2] and nxt[2] <= ev[2] \
                and (nxt[1], nxt[2]) != (ev[1], ev[2]):
            continue
        out.append(ev)
    return out


def host_spans(profile) -> list[tuple[str, float, float]]:
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += _events(line)
    return spans


def reduce_profile(profile, span_names: tuple[str, ...] = ()) -> dict | None:
    """The reduction described in the module's docstring; ``None`` where
    the trace holds no device plane."""
    spans = host_spans(profile)
    devices, asyncs = {}, {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                devices[int(m.group(1))] = _events(line)
            elif line.name == ASYNC_LINE:
                asyncs[int(m.group(1))] = _events(line)
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return None
    window = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if window:
        lo, hi = window[0]
    else:
        lo = min(e[1] for evs in devices.values() for e in evs)
        hi = max(e[2] for evs in devices.values() for e in evs)

    per_dev = []
    op_seconds: dict[str, float] = collections.defaultdict(float)
    op_counts: dict[str, float] = collections.defaultdict(float)
    for idx in sorted(devices):
        evs = [e for e in devices[idx] if e[2] > lo and e[1] < hi]
        busy = union(clip([(a, b) for _, a, b in evs], lo, hi))
        leaves = _leaves(evs)
        coll = union(clip([(a, b) for n, a, b in
                           leaves + asyncs.get(idx, [])
                           if COLLECTIVE.match(n)], lo, hi))
        other = union(clip([(a, b) for n, a, b in leaves
                            if not COLLECTIVE.match(n)], lo, hi))
        for n, a, b in leaves:
            op_seconds[n] += max(0.0, min(b, hi) - max(a, lo))
            op_counts[n] += 1
        per_dev.append(dict(device=idx, busy_s=total(busy), busy=busy,
                            collective_s=total(coll),
                            collective_exposed_s=total(subtract(coll, other))))
    n_dev = len(per_dev)

    # idle gaps of the first device, attributed to the benchmark's spans
    mine = [(n, a, b) for n, a, b in spans if n in span_names]
    gaps = subtract([(lo, hi)], per_dev[0]["busy"])
    by_span: dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        if b - a < MIN_GAP_S:
            by_span[LAUNCH_GAPS] += b - a
            continue
        best, best_cover = UNATTRIBUTED, 0.0
        for n, sa, sb in mine:
            cover = min(b, sb) - max(a, sa)
            if cover > best_cover:
                best, best_cover = n, cover
        by_span[best] += b - a
    return dict(
        window_s=hi - lo, devices=n_dev,
        busy_s=sum(d["busy_s"] for d in per_dev) / n_dev,
        busy_s_first=per_dev[0]["busy_s"],
        collective_s=sum(d["collective_s"] for d in per_dev) / n_dev,
        collective_exposed_s=sum(d["collective_exposed_s"]
                                 for d in per_dev) / n_dev,
        op_seconds={n: s / n_dev for n, s in op_seconds.items()},
        op_counts={n: c / n_dev for n, c in op_counts.items()},
        idle_gaps=dict(by_span))


def reduce_file(path: str, span_names: tuple[str, ...] = ()) -> dict | None:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path),
                          span_names)


def seconds_matching(op_seconds: dict[str, float], pattern: str) -> float:
    """Device seconds of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for n, s in op_seconds.items() if rx.search(n))


def top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])
            [:n]]


def describe(path: str, sample: int = 6) -> None:
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    for plane in profile.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:sample]:
                stats = [(k, str(v)[:120]) for k, v in e.stats]
                print(f"    {e.name[:100]!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} {stats[:8]}")


if __name__ == "__main__":
    describe(sys.argv[1])
