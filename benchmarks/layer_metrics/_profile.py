"""The run's own profile, for the readers that need more of it than
``trace_reduce.reduce_file`` keeps: device time per program, and the
device's idle time by the host span it falls in.

The window, the device events, the idle intervals and the host spans are
``trace_reduce``'s own (same functions, same definitions); the file is
parsed once per process.  What is added:

* ``program_ms(path, pattern)``: the median device duration, in ms, of the
  executions of the programs whose name matches ``pattern`` (line
  ``XLA Modules`` of the first device, one event per execution, named
  ``jit_<function>(<fingerprint>)``), counting executions that lie whole
  inside the traced window;
* ``idle_inside_pct(path, span)``: the first device's idle time that falls
  inside host spans of that name, as a share of the window.  A span is
  matched by its name up to any ``#`` (arguments may be appended to it);
* ``table(prof)``: programs by device time, and host and idle time by
  innermost span (each span's time less what its children cover), for
  reading by hand.  Printed once per traced run, when the first reader
  loads the profile, and by
  ``python -m benchmarks.layer_metrics._profile <file.xplane.pb>``.

All return ``None`` where there is no file, no device plane or, for a
span, no span of that name (a program without the span site).
"""

from __future__ import annotations

import collections
import functools
import glob
import os
import re
import statistics
import sys

from benchmarks import trace_reduce as tr

MODULES_LINE = "XLA Modules"
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_trace")
#: the spans of ``paddle_tpu/serving/engine.py`` (its one span site), for
#: the table; the serving job's own spans are added to them
ENGINE_SPANS = (
    "engine.step", "engine.admit", "engine.prefill",
    "engine.prefill_dispatch", "engine.first_token_sync", "engine.handoff",
    "engine.decode", "engine.decode_dispatch", "engine.decode_sync")
OUTSIDE = "outside_every_span"


def own_xplane(run: dict) -> str | None:
    """The profile this process wrote: ``run.py`` removes a cell's trace
    directory only after the readers ran, so when the run has a reduced
    trace the newest file under ``.bench_trace/*/plugins/profile/*/`` is
    its own.  ``None`` for a run without one."""
    if not run.get("trace"):
        return None
    found = glob.glob(os.path.join(TRACE_ROOT, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def digest(profile) -> dict | None:
    """``window`` (lo, hi), the first device's ``programs`` (name, start,
    end) and ``idle`` intervals, and the host ``spans`` by bare name."""
    ops, modules = {}, {}
    for plane in profile.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == tr.OPS_LINE:
                ops[int(m.group(1))] = tr._events(line)
            elif line.name == MODULES_LINE:
                modules[int(m.group(1))] = tr._events(line)
    ops = {k: v for k, v in ops.items() if v}
    if not ops:
        return None
    spans = collections.defaultdict(list)
    for name, a, b in tr.host_spans(profile):
        spans[name.partition("#")[0]].append((a, b))
    if spans.get(tr.WINDOW_SPAN):
        lo, hi = spans[tr.WINDOW_SPAN][0]
    else:
        lo = min(e[1] for evs in ops.values() for e in evs)
        hi = max(e[2] for evs in ops.values() for e in evs)
    first = min(ops)
    busy = tr.union(tr.clip([(a, b) for _, a, b in ops[first]], lo, hi))
    return dict(window=(lo, hi), idle=tr.subtract([(lo, hi)], busy),
                programs=[e for e in modules.get(first, [])
                          if e[1] >= lo and e[2] <= hi],
                spans=dict(spans))


@functools.lru_cache(maxsize=2)
def load(path: str | None) -> dict | None:
    if path is None:
        return None
    import jax

    prof = digest(jax.profiler.ProfileData.from_file(path))
    if prof is not None:
        print(table(prof), flush=True)
    return prof


def inside(intervals: list, cover: list, window: tuple) -> list:
    """The parts of ``intervals`` inside ``cover`` (any spans), in the
    window."""
    return tr.subtract(intervals, tr.subtract(
        [window], tr.union(tr.clip(cover, *window))))


def program_ms(path: str | None, pattern: str) -> float | None:
    prof = load(path)
    if prof is None:
        return None
    rx = re.compile(pattern)
    took = [b - a for name, a, b in prof["programs"] if rx.search(name)]
    return 1e3 * statistics.median(took) if took else None


def idle_inside_pct(path: str | None, span: str) -> float | None:
    prof = load(path)
    if prof is None or not prof["spans"].get(span):
        return None
    win = prof["window"]
    return 100.0 * tr.total(inside(prof["idle"], prof["spans"][span],
                                   win)) / (win[1] - win[0])


def innermost(spans: dict, names: tuple) -> dict:
    """name -> the intervals in which a span of that name is the innermost
    of the ``names`` open on the host (they nest: one thread opens them)."""
    out = collections.defaultdict(list)
    stack: list = []
    cur = 0.0
    nested = sorted(((a, -b, n) for n in names for a, b in spans.get(n, [])))
    for a, neg_b, name in nested + [(float("inf"), 0.0, None)]:
        while stack and stack[-1][1] <= a:
            top, end = stack.pop()
            if end > cur:
                out[top].append((cur, end))
                cur = end
        if stack and a > cur:
            out[stack[-1][0]].append((cur, a))
        cur = max(cur, a)
        stack.append((name, -neg_b))
    return out


def table(prof: dict) -> str:
    from benchmarks.jobs import serve

    lo, hi = prof["window"]
    win, idle = hi - lo, prof["idle"]
    rows = [f"profile: window {win:.4f} s, first device idle "
            f"{tr.total(idle):.4f} s ({100 * tr.total(idle) / win:.2f} %)",
            f"{'program':44} {'runs':>5} {'median ms':>10} {'total s':>9}"]
    by_program = collections.defaultdict(list)
    for name, a, b in prof["programs"]:
        by_program[name].append(b - a)
    for name, took in sorted(by_program.items(), key=lambda kv: -sum(kv[1])):
        rows.append(f"{name[:44]:44} {len(took):5d} "
                    f"{1e3 * statistics.median(took):10.3f} "
                    f"{sum(took):9.4f}")
    own = innermost(prof["spans"], ENGINE_SPANS + serve.SPANS)
    covered = tr.union([iv for ivs in own.values() for iv in ivs])
    own[OUTSIDE] = tr.subtract([(lo, hi)], covered)
    rows.append(f"{'innermost span':28} {'spans':>6} {'host s':>9} "
                f"{'host %':>7} {'idle s':>9} {'idle % of window':>17}")
    for name in sorted(own, key=lambda n: -tr.total(tr.clip(own[n], lo, hi))):
        mine = tr.union(tr.clip(own[name], lo, hi))
        gap = tr.total(inside(idle, mine, (lo, hi)))
        rows.append(f"{name:28} {len(prof['spans'].get(name, [])):6d} "
                    f"{tr.total(mine):9.4f} {100 * tr.total(mine) / win:7.2f} "
                    f"{gap:9.4f} {100 * gap / win:17.3f}")
    return "\n".join(rows)


if __name__ == "__main__":
    if load(sys.argv[1]) is None:
        sys.exit(f"{sys.argv[1]}: no device plane")
