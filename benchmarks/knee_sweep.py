"""Find a serving mix's knee once, on the chip: one engine, several offered
rates in turn, one line of JSON per rate.

    python benchmarks/knee_sweep.py --workload <serving cell> --seed 1 \
        --seconds 40 --rates 4,6,8,10,12

Not part of a check: the cells run at the fixed ``rate_rps`` this sweep led
to (``PERF.md`` records the sweep).  The knee is the highest rate at which
the backlog (requests waiting or in a slot) at the end of the run is no
larger than a third of the way in and, for a mix served below capacity, at
least 90% of the scored requests met both limits.  Give ``lead_s`` in the
cell's file at least one request's lifetime, or every rate reads as a ramp.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
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
    args = ap.parse_args()

    from benchmarks import run, sut, weights
    from benchmarks.jobs import serve

    cell = run.load_json(os.path.join(HERE, "workloads", f"{args.workload}.json"))
    run.require_tpu(cell["chips"])
    config = run.load_json(os.path.join(HERE, "configs", f"{cell['config']}.json"))
    traffic = run.load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
    ctx = run.Context(cell=cell, config=config, traffic=traffic,
                      sizes=weights.sizes(config), seed=args.seed,
                      seconds=args.seconds,
                      tracer=run.WindowTracer(False, "", 0.0),
                      t_process=_T_PROCESS, spans=serve.SPANS)
    sut.configure_compile_cache()
    server = serve.Server(ctx)
    checks = serve.reference_check(server, ctx)
    print(json.dumps({"checks": checks,
                      "paths": server.engine.attention_paths()}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        while server.engine.has_work:       # what the last rate left behind
            server.engine.step()
        server.token_times.clear()
        sched = serve.make_schedule(ctx, rate, args.seconds)
        out = serve.score(server, ctx, serve.drive(server, ctx, sched,
                                                   args.seconds))
        r = out["run"]
        print(json.dumps({
            "rate_rps": rate, "scored": out["attempted"],
            "failed": out["failed"], "values": out["values"],
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
