"""The benchmark's command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  Finds the cell in ``BENCHMARK.json`` and its files by name
(``workloads/<cell>.json``, ``configs/<config>.json``, ``traffic/<mix>.json``,
``jobs/<job>.py``, and for every per-layer metric ``BENCHMARK.json`` lists
for the cell ``layer_metrics/<reader>.py``, the reader being the metric's
name after its last dot: ``a.device_idle_pct`` and ``b.device_idle_pct`` are
one reader under two names, because a per-layer metric moves one
end-to-end metric and is reported only where that one is), refuses to run
without a TPU holding the chips the cell asks for, sets up (weights from the
seed, reference check, every shape warmed), measures for ``--seconds`` and
prints as the last line of stdout one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, from counters over the whole window and
from a profiler trace of the window's last ``trace_seconds``.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class WindowTracer:
    """Profiles the last ``trace_seconds`` of the window when enabled; off,
    every call is a no-op."""

    def __init__(self, enabled: bool, trace_dir: str, start_at: float):
        self.enabled, self.dir, self.start_at = enabled, trace_dir, start_at
        self._stack = None
        self.path = None

    def _start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def warm(self) -> None:
        """A throwaway session in set-up, so that the profiler's own
        start-up does not stall the window."""
        if not self.enabled:
            return
        import jax
        import jax.numpy as jnp

        shutil.rmtree(self.dir, ignore_errors=True)
        self._start()
        jax.block_until_ready(jnp.zeros((8, 128)) + 1)
        jax.profiler.stop_trace()
        shutil.rmtree(self.dir, ignore_errors=True)

    def poll(self, t_rel: float) -> None:
        if self.enabled and self._stack is None and self.path is None \
                and t_rel >= self.start_at:
            import jax

            from benchmarks import trace_reduce

            self._start()
            self._stack = contextlib.ExitStack()
            self._stack.enter_context(
                jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN))

    def close(self) -> None:
        if self._stack is None:
            return
        import jax

        from benchmarks import trace_reduce

        self._stack.close()
        self._stack = None
        jax.profiler.stop_trace()
        self.path = trace_reduce.newest_xplane(self.dir)


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    sizes: dict
    seed: int
    seconds: float
    tracer: WindowTracer
    t_process: float
    spans: tuple[str, ...] = ()     # the job's host spans (its ``SPANS``)
    setup_s: float | None = None

    def span(self, name: str):
        import jax

        assert name in self.spans, name
        return jax.profiler.TraceAnnotation(name)

    def log(self, msg: str) -> None:
        print(f"[{time.perf_counter() - self.t_process:7.1f}s] {msg}",
              flush=True)

    def mark(self, phase: str) -> None:
        """Set-up phases, printed so that a long set-up can be read."""
        self.log(f"set-up: {phase} done")

    def window_open(self) -> None:
        self.setup_s = time.perf_counter() - self.t_process
        self.log(f"window opens after {self.setup_s:.2f}s of set-up")


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero without a result line
    unless it is a TPU with at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"benchmarks/run.py: JAX's default backend is "
                 f"{devs[0].platform!r}, not a TPU; refusing to measure")
    if len(devs) < chips:
        sys.exit(f"benchmarks/run.py: the cell needs {chips} chips, JAX "
                 f"found {len(devs)}")
    return device_info()


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks, default=0))


def execute(bench_file: str, root: str, workload: str, seed: int,
            seconds: float, trace: bool, device: dict,
            t_process: float) -> dict:
    """Run one cell whose files live under ``root`` and whose entry is in
    ``bench_file``; returns the result object.  ``main`` gives the
    repository's own; the tests give a tiny set on the CPU."""
    bench = load_json(bench_file)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        sys.exit(f"no workload {workload!r} in {bench_file}")
    cell = load_json(os.path.join(root, "workloads", f"{workload}.json"))
    if (cell["config"], cell["traffic"], cell["chips"]) != (
            entry["config"], entry["traffic"], entry["chips"]):
        sys.exit(f"{workload}: the cell's file and BENCHMARK.json disagree")
    config = load_json(os.path.join(root, "configs", f"{cell['config']}.json"))
    traffic = load_json(os.path.join(root, "traffic", f"{cell['traffic']}.json"))

    from benchmarks import flops, sut, trace_reduce, weights

    trace_dir = os.path.join(CHECKOUT, ".bench_trace", workload)
    ctx = Context(
        cell=cell, config=config, traffic=traffic,
        sizes=weights.sizes(config), seed=seed, seconds=seconds,
        tracer=WindowTracer(trace, trace_dir,
                            max(0.0, seconds - cell["trace_seconds"])),
        t_process=t_process)
    ctx.log(f"compile cache: {sut.configure_compile_cache()}")
    ctx.tracer.warm()
    job = importlib.import_module(f"benchmarks.jobs.{cell['job']}")
    ctx.spans = job.SPANS
    out = job.run(ctx)
    for name, ok in out["checks"].items():
        if not ok:
            ctx.log(f"CHECK FAILED: {name}")

    def in_cell(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    result = {"correct": all(out["checks"].values()),
              "attempted": out["attempted"], "failed": out["failed"]}
    device = dict(device, memory_peak_bytes=memory_peak_bytes(cell["chips"]))
    metrics = {}
    if not trace:
        values = dict(out["values"], setup_s=ctx.setup_s)
        for m in bench["end_to_end"]:
            if in_cell(m) and values.get(m["name"]) is not None:
                metrics[m["name"]] = values[m["name"]]
    else:
        reduced = (trace_reduce.reduce_file(ctx.tracer.path, job.SPANS)
                   if ctx.tracer.path else None)
        run = dict(out["run"], trace=reduced, sizes=ctx.sizes, cell=cell,
                   config=config, values=out["values"],
                   peaks=flops.peaks(device["kind"])
                   if device["platform"] == "tpu" else None)
        for m in bench["per_layer"]:
            if not in_cell(m):
                continue
            reader = importlib.import_module(
                f"benchmarks.layer_metrics.{m['name'].rpartition('.')[2]}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = value
        if reduced:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            result["breakdown"] = {
                "device_ops": trace_reduce.top(reduced["op_seconds"]),
                "idle_gaps": trace_reduce.top(reduced["idle_gaps"])}
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()}
    result["device"] = device
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    bench_file = os.path.join(CHECKOUT, "BENCHMARK.json")
    chips = next((w["chips"] for w in load_json(bench_file)["workloads"]
                  if w["name"] == args.workload), None)
    if chips is None:
        sys.exit(f"no workload {args.workload!r} in BENCHMARK.json")
    import paddle_tpu  # noqa: F401  (fails here where the program is absent)

    device = require_tpu(chips)
    result = execute(bench_file, HERE, args.workload, args.seed,
                     args.seconds, bool(args.trace), device, _T_PROCESS)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
