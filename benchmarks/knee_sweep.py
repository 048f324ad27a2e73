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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--lead", type=float, help="instead of the cell's lead_s")
    args = ap.parse_args()

    import numpy as np

    from benchmarks import run, sut, weights

    cell = run.load_json(os.path.join(HERE, "workloads", f"{args.workload}.json"))
    if args.lead is not None:
        cell["lead_s"] = args.lead
    job = importlib.import_module(f"benchmarks.jobs.{cell['job']}")
    run.require_tpu(cell["chips"])
    config = run.load_json(os.path.join(HERE, "configs", f"{cell['config']}.json"))
    traffic = run.load_json(os.path.join(HERE, "traffic",
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
    print(json.dumps({"checks": checks,
                      "paths": server.engine.attention_paths()}), flush=True)
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
        print(json.dumps({
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
            / max(r["stats"]["decode_calls"], 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
