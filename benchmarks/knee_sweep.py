"""Find a serving mix's knee once, on the chip: one engine, several offered
rates in turn, one line of JSON per rate.

    python benchmarks/knee_sweep.py --workload <serving cell> --seed 1 \
        --seconds 40 --rates 4,6,8,10,12

Not part of a check: the cells run at the fixed ``rate_rps`` this sweep led
to (``PERF.md`` records the sweep).  The knee is the highest rate at which
the backlog (requests waiting or in a slot) at the end of the run is no
larger than a third of the way in and, for a mix served below capacity, at
least 90% of the scored requests met both limits.  The job is the one the
cell's file names, as in ``run.py``.  The lead-in is the cell's ``lead_s``
unless ``--lead`` gives another: three request lifetimes or more
(``lifetime_p95_s`` of each line), or every rate reads as a ramp.

``--requests`` follows each rate's line with one line per request that was
scored or whose first token fell inside the window (what ``served_rate``
counts), and with the engine's own rate over the window: to run down a run
that reads far from its neighbours.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def request_lines(server, obs: dict) -> list[dict]:
    """One record per request that was due inside the window (``scored``) or
    whose first token fell inside it: times on the window's clock, None for
    what never came.  ``in_served_interval``: ``served_rate`` counts its
    tokens if it ``completed`` (the window's earliest first token only opens
    the interval)."""
    seconds, origin = obs["seconds"], obs["origin"]
    rows = []
    for rid, (req, _) in obs["sent"].items():
        times = server.token_times.get(rid)
        first = times[0] - origin if times else None
        inside = first is not None and 0 <= first < seconds
        scored = 0 <= req.due < seconds
        if not (inside or scored):
            continue
        fin = obs["finished"].get(rid)
        rows.append({"request": rid, "due": req.due,
                     "prompt": len(req.prompt), "new": req.max_new,
                     "scored": scored, "first_token": first,
                     "completed": fin[1] if fin is not None and fin[0].ok
                     else None, "in_served_interval": inside})
    opener = min((r for r in rows if r["in_served_interval"]),
                 key=lambda r: r["first_token"], default=None)
    if opener is not None:
        opener["in_served_interval"] = False
    return rows


def engine_rate(server, obs: dict, stats: dict) -> dict:
    """What the engine itself counted over the window, per second: tokens
    sampled, chunk dispatches and, where the model counts its expert rows
    (every row of every dispatch, prompt or decode, passes every expert
    layer once), the rows it processed.  No request is cut at an edge.
    ``longest_step_ms``: the longest time from the end of one step to the
    end of the next inside the window, so that a stall shows as one."""
    seconds = obs["seconds"]
    ends = [t for t, _ in obs["depth"] if 0 <= t < seconds]
    out = {"generated_tokens_per_s": stats["tokens_generated"] / seconds,
           "prefill_calls_per_s": stats["prefill_calls"] / seconds,
           "longest_step_ms": 1e3 * max(
               (b - a for a, b in zip(ends, ends[1:])), default=0.0)}
    sz = getattr(server, "sz", None)
    if sz and stats.get("moe_assignments"):
        out["rows_per_s"] = (stats["moe_assignments"]
                             / (sz["top_k"] * sz["layers"]) / seconds)
    return out


def sweep(root: str, args, emit) -> None:
    """``args`` as ``main`` parses them; the cell's files under ``root``;
    every line goes to ``emit`` as a dict."""
    import numpy as np

    from benchmarks import run, sut, weights

    cell = run.load_json(os.path.join(root, "workloads", f"{args.workload}.json"))
    if args.lead is not None:
        cell["lead_s"] = args.lead
    job = importlib.import_module(f"benchmarks.jobs.{cell['job']}")
    config = run.load_json(os.path.join(root, "configs", f"{cell['config']}.json"))
    traffic = run.load_json(os.path.join(root, "traffic",
                                     f"{cell['traffic']}.json"))
    ctx = run.Context(cell=cell, config=config, traffic=traffic,
                      sizes=weights.sizes(config), seed=args.seed,
                      seconds=args.seconds,
                      tracer=run.WindowTracer(False, "", 0.0),
                      t_process=_T_PROCESS, spans=job.SPANS)
    sut.configure_compile_cache()
    server = job.Server(ctx)
    checks = job.reference_check(server, ctx)
    server.weights = None
    emit({"checks": checks, "paths": server.engine.attention_paths()})
    for rate in (float(r) for r in args.rates.split(",")):
        while server.engine.has_work:       # what the last rate left behind
            server.engine.step()
        server.token_times.clear()
        sched = job.make_schedule(ctx, rate, args.seconds)
        obs = job.drive(server, ctx, sched, args.seconds)
        out = job.score(server, ctx, obs)
        r = out["run"]
        scored = [(req, obs["finished"].get(rid))
                  for rid, (req, _) in obs["sent"].items()
                  if 0 <= req.due < args.seconds]
        # due to completion, of the requests due in the window's first half
        # (the drain may stop before the later ones end); one that never
        # ended counts as long as it was watched
        lives = [(obs["drained_at"] if fin is None else fin[1]) - req.due
                 for req, fin in scored if req.due < args.seconds / 2]
        emit({
            "rate_rps": rate, "lead_s": cell["lead_s"],
            "scored": out["attempted"],
            "failed": out["failed"], "values": out["values"],
            "offered_tokens_per_s": sum(
                len(req.prompt) + req.max_new for req, _ in scored)
            / args.seconds,
            "lifetime_p95_s": float(np.percentile(lives, 95))
            if lives else None,
            "gap_ms": r["gap_ms"],
            "decode_calls_after_chunks": [
                r["stats"].get(f"decode_calls_after_{k}")
                for k in ("0_chunks", "1_chunk", "2plus_chunks")],
            **{k: r[k] for k in (
                "slo_attained_pct", "ttft_p50_ms", "ttft_p95_ms",
                "completed_tokens_per_s", "backlog_third", "backlog_end",
                "drain_s", "generator_late_p95_ms", "pages_peak",
                "window_compiles")},
            "preemptions": r["stats"]["preemptions"],
            "decode_batch_mean": r["stats"]["tokens_generated"]
            / max(r["stats"]["decode_calls"], 1)})
        if args.requests:
            for row in request_lines(server, obs):
                emit(row)
            emit({"rate_rps": rate,
                  "engine": engine_rate(server, obs, r["stats"])})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--lead", type=float, help="instead of the cell's lead_s")
    ap.add_argument("--requests", action="store_true",
                    help="a line per request and the engine's own rate")
    args = ap.parse_args()

    from benchmarks import run

    chips = run.load_json(os.path.join(
        HERE, "workloads", f"{args.workload}.json"))["chips"]
    run.require_tpu(chips)
    sweep(HERE, args, lambda line: print(json.dumps(line), flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
