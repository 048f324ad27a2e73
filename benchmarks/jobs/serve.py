"""The serving job: an open-loop schedule of seeded requests against one
``ServingEngine``, timed from the client's side.

Cell keys read here: ``rate_rps`` (requests per second offered; fixed, found
once by the knee sweep), ``lead_s`` (the schedule starts this long before
the window so that the window opens on a loaded engine), ``drain_until``
and ``drain_s`` (arrivals stop at the window's end; the engine is then
stepped until every scored request has its ``first_token``, or is
``complete``, and for ``drain_s`` seconds at most) and ``limits``
(``ttft_ms``, ``gap_ms``: what a request must meet to count as attained).
Configuration keys: ``dtype``, ``engine`` (sizes only), ``check``.

One thread: due requests are handed to ``add_request``, then the engine
steps; with no work it sleeps to the next due time.  Every token is timed
in the benchmark's ``on_token``.  Times to first token count from the time
a request was *due*, so a stall is charged to every request it delays.

Scored are requests due inside the window.  One that was rejected, expired,
cancelled by the engine, ended with a wrong token count or an id outside
the vocabulary, or had not reached what the drain waits for when the drain
ended, is ``failed`` and misses every limit.  What is still queued or
decoding when the run stops is cancelled.
"""

from __future__ import annotations

import importlib
import time

import jax.numpy as jnp
import numpy as np

from benchmarks import sut, weights
from benchmarks.reference import gpt2_ref

#: the job's host spans; idle gaps of the device are attributed to
#: whichever of them covers the gap
SPANS = ("add_request", "engine_step", "generator_sleep")

#: prompts of the set-up check: with the engine's chunk buckets (powers of
#: two from 8 to ``chunk_tokens``=128) their last chunks are 8, 14, 23, 45
#: and 101 rows, so the five prefill programs and the decode program are
#: all compiled and checked before the window
CHECK_PROMPTS = (8, 14, 151, 301, 229)
CHECK_NEW = 12


class Server:
    """The engine with the benchmark's clock on every token."""

    def __init__(self, ctx):
        cfg, sz = ctx.config, ctx.sizes
        model = sut.build_model(sz, parallel=False, seed=ctx.seed)
        self.weights = weights.make(cfg, ctx.seed, cfg["dtype"])
        sut.load_weights(model, self.weights)
        ctx.mark("weights")
        self.token_times: dict[int, list[float]] = {}
        self.engine = sut.build_engine(
            model, sizes=cfg["engine"], seed=ctx.seed, on_token=self._on_token)
        ctx.mark("engine")

    def _on_token(self, rid: int, tok: int) -> None:
        self.token_times.setdefault(rid, []).append(time.perf_counter())

    def run_to_completion(self, prompts, max_new) -> list:
        eng = self.engine
        rids = [eng.add_request(p, max_new) for p in prompts]
        done = {}
        while eng.has_work:
            for fin in eng.step():
                done[fin.rid] = fin
        return [done[r] for r in rids]


def reference_check(server: Server, ctx) -> dict:
    """Five greedy requests through chunked prefill and paged decode; the
    reference is run teacher-forced along the tokens the engine emitted and
    every emitted token's reference logit must lie within a margin of the
    reference's maximum at that position."""
    sz, tol = ctx.sizes, ctx.config["check"]
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    prompts = [rng.integers(0, sz["vocab"], n).astype(np.int32)
               for n in CHECK_PROMPTS]
    # one at a time, so that the chunking does not depend on the budget
    fins = [server.run_to_completion([p], CHECK_NEW)[0] for p in prompts]
    width = max(CHECK_PROMPTS) + CHECK_NEW
    rows = np.zeros((len(prompts), width), np.int32)
    for i, (p, fin) in enumerate(zip(prompts, fins)):
        toks = np.asarray(fin.tokens)
        if not fin.ok or toks.shape != (CHECK_NEW,):
            ctx.log(f"serve check: request {i} ended {fin.finish_reason!r} "
                    f"with {toks.shape} tokens")
            return {"reference_logits": False}
        rows[i, :len(p)] = p
        rows[i, len(p):len(p) + CHECK_NEW] = toks
    hid = gpt2_ref.hidden(server.weights, rows, n_head=sz["heads"],
                          eps=sz["eps"])
    # row i, positions P-1 .. P+CHECK_NEW-2 predict the emitted tokens
    idx = np.stack([np.arange(len(p) - 1, len(p) - 1 + CHECK_NEW)
                    for p in prompts])
    picked = jnp.take_along_axis(hid, jnp.asarray(idx)[:, :, None], axis=1)
    w = server.weights
    lg = np.asarray(gpt2_ref.head(picked, w["lnf_g"], w["lnf_b"], w["wte"],
                                   eps=sz["eps"]))
    emitted = np.stack([np.asarray(f.tokens) for f in fins])
    short = lg.max(-1) - np.take_along_axis(lg, emitted[:, :, None], -1)[..., 0]
    same = float((lg.argmax(-1) == emitted).mean())
    ctx.log(f"serve check: reference-logit shortfall of the emitted tokens "
            f"mean {short.mean():.4f} max {short.max():.4f} (tol mean "
            f"{tol['serve_logit_shortfall_mean']}, max "
            f"{tol['serve_logit_shortfall_max']}); {same:.0%} are the "
            f"reference's argmax; top logit {lg.max(-1).mean():.2f}")
    return {"reference_logits":
            bool(short.mean() <= tol["serve_logit_shortfall_mean"]
                 and short.max() <= tol["serve_logit_shortfall_max"])}


def make_schedule(ctx, rate: float, seconds: float) -> list:
    cell = ctx.cell
    return importlib.import_module(
        f"benchmarks.traffic.{ctx.traffic['generator']}").make(
        ctx.traffic, vocab=ctx.sizes["vocab"], seed=ctx.seed, rate=rate,
        start=-float(cell["lead_s"]), end=seconds,
        max_total=ctx.config["engine"]["max_seq_len"])


def drive(server: Server, ctx, schedule: list, seconds: float) -> dict:
    """Run ``schedule`` against the engine; the window is [0, seconds) on
    the schedule's clock.  Returns the raw observations."""
    eng, cell = server.engine, ctx.cell
    stats0 = stats1 = None
    sent: dict[int, tuple] = {}      # rid -> (request, handed over at)
    finished: dict[int, object] = {}
    pages, depth = [], []            # (t, value) samples after each step
    i, n = 0, len(schedule)
    until = cell["drain_until"]
    if until not in ("first_token", "complete"):
        raise ValueError(f"drain_until: {until!r}")
    end, closing = seconds + float(cell["drain_s"]), 0.0
    origin = time.perf_counter() + float(cell["lead_s"])

    def now() -> float:
        return time.perf_counter() - origin

    while True:
        t = now()
        if stats0 is None and t >= 0:
            stats0 = dict(eng.stats)
            ctx.window_open()
        if t >= 0:
            ctx.tracer.poll(t)
        while i < n and schedule[i].due <= t:
            req = schedule[i]
            with ctx.span("add_request"):
                rid = eng.add_request(req.prompt, req.max_new)
            sent[rid] = (req, now())
            i += 1
        if t >= seconds:
            if stats1 is None:
                stats1 = dict(eng.stats)
                ctx.tracer.close()
                # writing a profile takes seconds in which nothing is
                # stepped: they are not the drain's
                closing = now() - t
                end += closing
            waiting = any(req.due >= 0 and rid not in finished
                          and (until == "complete"
                               or rid not in server.token_times)
                          for rid, (req, _) in sent.items())
            if not waiting or t >= end:
                break
        if eng.has_work:
            with ctx.span("engine_step"):
                for fin in eng.step():
                    finished[fin.rid] = (fin, now())
            pages.append((now(), eng.stats["pages_in_use"]))
            depth.append((now(), eng.scheduler.n_waiting
                          + eng.scheduler.n_active))
        else:
            wake = schedule[i].due if i < n else seconds
            with ctx.span("generator_sleep"):
                time.sleep(max(0.0, min(wake - now(), 0.05)))
    for rid in sent:                 # stop, do not wait
        if rid not in finished:
            eng.cancel(rid)
    return dict(sent=sent, finished=finished, pages=pages, depth=depth,
                stats0=stats0 or dict(eng.stats), stats1=stats1,
                origin=origin, seconds=seconds, drained_at=now() - closing)


def served_rate(server: Server, obs: dict, good: set) -> float | None:
    """Tokens per second of requests served through to their end.  The
    interval runs from the first to the last first-token inside the window;
    a request whose first token fell in it (its prompt is then ingested)
    counts with its prompt and output tokens, and only if it went on to
    complete with the right tokens (``good``) before the drain ended.  Both
    ends are events of the serialized prefill, so nothing is cut at a window
    edge; the times of completions, which trail by an output's length, move
    a count over a fixed window by a request's worth from seed to seed."""
    seconds, origin = obs["seconds"], obs["origin"]
    firsts = sorted((times[0] - origin, rid)
                    for rid, times in server.token_times.items()
                    if rid in obs["sent"] and 0 <= times[0] - origin < seconds)
    if len(firsts) < 3:
        return None
    tokens = sum(len(obs["sent"][rid][0].prompt) + obs["sent"][rid][0].max_new
                 for _, rid in firsts[1:] if rid in good)
    return tokens / (firsts[-1][0] - firsts[0][0])


def _pct(xs: list, q: float) -> float | None:
    return float(np.percentile(xs, q)) if xs else None


def _ms(x: float | None) -> float | None:
    return None if x is None else x * 1e3


def gap_levels(gaps: list) -> dict | None:
    """Where the 95th percentile of the gaps stands among their levels (a
    gap is a decode, the chunks before it and the host's turn): some
    quantiles in ms, and the share of gaps more than a fifth above it, and
    more than a sixth below.  A 95th percentile with 2-8 % of the gaps on a
    level above its own stands on an edge and tips with the arrivals."""
    if not gaps:
        return None
    g = np.asarray(gaps) * 1e3
    qs = (5, 25, 50, 75, 90, 95, 98, 99.5)
    out = {f"p{q:g}": float(v) for q, v in zip(qs, np.percentile(g, qs))}
    p95 = out["p95"]
    out["over_1.2_p95_pct"] = 100.0 * float((g > 1.2 * p95).mean())
    out["under_p95_over_1.2_pct"] = 100.0 * float((g < p95 / 1.2).mean())
    return {k: round(v, 3) for k, v in out.items()}


def score(server: Server, ctx, obs: dict) -> dict:
    """From raw observations to the numbers a user of the service sees."""
    sz, cell = ctx.sizes, ctx.cell
    seconds, origin = obs["seconds"], obs["origin"]
    lim = cell["limits"]
    ttft, gaps, late = [], [], []
    attempted = failed = attained = 0
    done_tokens = done_requests = bad_tokens = 0
    good = set()     # completed with the right number of tokens, in range
    for rid, (req, sent_at) in obs["sent"].items():
        times = [x - origin for x in server.token_times.get(rid, [])]
        fin = obs["finished"].get(rid)
        gaps += [b - a for a, b in zip(times, times[1:]) if 0 <= b < seconds]
        if fin is not None and fin[0].ok:
            toks = np.asarray(fin[0].tokens)
            if toks.shape == (req.max_new,) and toks.min() >= 0 \
                    and toks.max() < sz["vocab"]:
                good.add(rid)
                if 0 <= fin[1] < seconds:
                    done_requests += 1
                    done_tokens += len(req.prompt) + req.max_new
            else:
                bad_tokens += 1
        if not 0 <= req.due < seconds:
            continue
        attempted += 1
        late.append(sent_at - req.due)
        if (not times or (fin is not None and rid not in good)
                or (fin is None and cell["drain_until"] == "complete")):
            failed += 1
            continue
        ttft.append(times[0] - req.due)
        mean_gap = ((times[-1] - times[0]) / (len(times) - 1)
                    if len(times) > 1 else 0.0)
        attained += (ttft[-1] * 1e3 <= lim["ttft_ms"]
                     and mean_gap * 1e3 <= lim["gap_ms"])
    s0, s1 = obs["stats0"], obs["stats1"]
    delta = {k: s1[k] - s0[k] for k in s1
             if isinstance(s1[k], (int, float)) and not k.startswith("last_")}

    def backlog(lo: float, hi: float) -> float:
        """Mean number of requests waiting or in a slot over [lo, hi)."""
        return float(np.mean([v for t, v in obs["depth"] if lo <= t < hi]
                             or [0]))

    values = {
        "serve_tokens_per_s": served_rate(server, obs, good),
        "tbt_p95_ms": _ms(_pct(gaps, 95)),
    }
    run = dict(
        window_s=seconds, stats=delta, scored=attempted,
        ttft_p50_ms=_ms(_pct(ttft, 50)), ttft_p95_ms=_ms(_pct(ttft, 95)),
        completed_tokens_per_s=done_tokens / seconds,
        drain_s=obs["drained_at"] - seconds,
        backlog_third=backlog(seconds / 3 - 1, seconds / 3 + 1),
        backlog_end=backlog(seconds - 2, seconds),
        slo_attained_pct=100.0 * attained / attempted if attempted else None,
        generator_late_p95_ms=_ms(_pct(late, 95)),
        pages_peak=max((v for t, v in obs["pages"] if 0 <= t < seconds),
                       default=0),
        num_pages=ctx.config["engine"]["num_pages"],
        done_requests=done_requests, n_gaps=len(gaps),
        gap_ms=gap_levels(gaps),
        window_compiles=delta["prefill_traces"] + delta["decode_traces"])
    checks = {"token_counts_in_range": bad_tokens == 0,
              "some_request_completed": done_requests > 0}
    return dict(checks=checks, attempted=attempted, failed=failed,
                values=values, run=run)


def run(ctx) -> dict:
    server = Server(ctx)
    checks = reference_check(server, ctx)
    paths = server.engine.attention_paths()
    checks["compiled_kernels"] = all(v == "kernel" for v in paths.values())
    st = server.engine.stats
    ctx.log(f"serve: attention paths {paths}; after the check "
            f"{st['prefill_traces']} prefill and {st['decode_traces']} decode "
            f"programs traced")
    ctx.mark("check_and_warm")
    server.weights = None
    server.token_times.clear()
    schedule = make_schedule(ctx, float(ctx.cell["rate_rps"]), ctx.seconds)
    out = score(server, ctx, drive(server, ctx, schedule, ctx.seconds))
    out["checks"].update(checks)
    r, v = out["run"], out["values"]
    ctx.log(f"serve: {out['attempted']} scored, {out['failed']} failed, "
            f"{r['done_requests']} completed in the window "
            f"({r['completed_tokens_per_s']:.0f} tokens/s), served "
            f"{v['serve_tokens_per_s']} tokens/s, ttft p50 "
            f"{r['ttft_p50_ms']} p95 {r['ttft_p95_ms']} ms, gap p95 "
            f"{v['tbt_p95_ms']} ms over {r['n_gaps']} gaps ({r['gap_ms']}), "
            f"generator late "
            f"p95 {r['generator_late_p95_ms']} ms, backlog "
            f"{r['backlog_third']:.1f} a third in and {r['backlog_end']:.1f} "
            f"at the end, drained {r['drain_s']:.1f} s after it")
    return out
