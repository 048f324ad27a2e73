"""The serving job for a ``cohere2_moe`` share: ``jobs/serve.py``'s open
loop, drive and score, against an engine that serves that model.

Its own: how the model is built (the seeded tree of ``weights_cohere2`` is
adopted as it is, so the weights exist once), the reference check
(``reference/cohere2_moe_ref.py``, with two prompts long enough that the
sliding window masks, the window ring turns and pages are recycled during
prefill, served in one batch), and the window group's page count, sampled
beside the full group's.  Cell and configuration keys are ``jobs/serve.py``'s.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import sut, weights_cohere2
from benchmarks.jobs import serve
# run.py reads SPANS; knee_sweep.py drives any serving job by these names
from benchmarks.jobs.serve import SPANS, drive, make_schedule, score  # noqa: F401,E501
from benchmarks.reference import cohere2_moe_ref as ref

#: the five prompts of ``jobs/serve.py`` (their last chunks fall in the five
#: chunk buckets), one at a time; then two past the sliding window in one
#: batch, where the mask, the ring and recycling act in two slots at once
#: (the second slot's ring lies at an offset the first one's does not
#: have); the engine's ``max_seq_len`` caps them at tiny sizes
CHECK_PROMPTS = serve.CHECK_PROMPTS + (6007, 4391)
TOGETHER = 2
#: tokens checked per prompt: a share of 7 x 48 tokens has a third of the
#: scatter of a share of 7 x 12
CHECK_NEW = 48


def model_config(sz: dict, config: dict):
    from paddle_tpu.models import Cohere2MoeConfig

    cfg = Cohere2MoeConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], num_heads=sz["heads"],
        num_kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        intermediate_size=sz["expert_width"], num_experts=sz["router_width"],
        num_experts_per_tok=sz["top_k"], num_shared_experts=sz["shared"],
        experts_held=sz["experts_held"], sliding_window=sz["window"],
        layer_switch=sz["period"], rope_theta=sz["theta"],
        layer_norm_eps=sz["eps"], norm_topk_prob=config["norm_topk_prob"],
        logit_scale=config["logit_scale"],
        max_seq_len=config["max_position_embeddings"], dtype=config["dtype"])
    kinds = ["full_attention" if cfg.window_of(li) is None
             else "sliding_attention" for li in range(cfg.num_layers)]
    assert kinds == sz["layer_types"], (kinds, sz["layer_types"])
    return cfg


class Server(serve.Server):
    def __init__(self, ctx):
        # a program without the model fails here, before nine gigabytes
        # of weights are made for it
        from paddle_tpu.models import Cohere2MoeForCausalLM

        cfg = ctx.config
        self.sz = weights_cohere2.sizes(cfg)
        self.weights = weights_cohere2.make(cfg, ctx.seed, cfg["dtype"])
        model = Cohere2MoeForCausalLM(model_config(self.sz, cfg),
                                      weights=self.weights)
        ctx.mark("weights")
        self.token_times: dict[int, list[float]] = {}
        #: what ``reference_check`` compared, for ``precision_study``
        self.checked: dict | None = None
        #: (time, live pages of the window group) where it changed
        self.window_pages: list[tuple[float, int]] = [(0.0, 0)]
        self.engine = sut.build_engine(
            model, sizes=cfg["engine"], seed=ctx.seed, on_token=self._on_token)
        ctx.mark("engine")

    def _on_token(self, rid: int, tok: int) -> None:
        now = time.perf_counter()
        self.token_times.setdefault(rid, []).append(now)
        live = self.engine.stats.get("pages_in_use_window")
        if live is not None and live != self.window_pages[-1][1]:
            self.window_pages.append((now, live))


def emitted_tokens(server: Server, ctx) -> tuple[list, list]:
    """(prompts, finished requests) of the check: greedy, through chunked
    prefill and paged decode, the last ``TOGETHER`` prompts in one batch."""
    cap = ctx.config["engine"]["max_seq_len"] - CHECK_NEW
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    prompts = [rng.integers(0, server.sz["vocab"],
                            min(n, cap)).astype(np.int32)
               for n in CHECK_PROMPTS]
    fins = [server.run_to_completion([p], CHECK_NEW)[0]
            for p in prompts[:-TOGETHER]]
    return prompts, fins + server.run_to_completion(prompts[-TOGETHER:],
                                                    CHECK_NEW)


def reference_logits(server: Server, ctx, prompts, emitted):
    """The reference teacher-forced along ``emitted`` (n, CHECK_NEW):
    its logits (n, CHECK_NEW, vocab) at the positions that predict those
    tokens, and the router's margin there (n, CHECK_NEW): the gap between
    the ``top_k``-th and the next router logit over the size of the first,
    the smallest over the layers in which one of the two experts is held
    (a flip between two absent experts changes nothing), inf where none."""
    sz, w = server.sz, server.weights
    windows = [sz["window"] if kind == "sliding_attention" else None
               for kind in sz["layer_types"]]
    first, count = sz["experts_held"]
    k = sz["top_k"]
    rows, margins = [], []
    for p, toks in zip(prompts, emitted):
        # padded on the right to whole blocks of rows: under a causal mask
        # the padding changes no earlier position, and lengths share shapes
        seq = np.concatenate([p, toks[:-1]])
        ids = np.zeros((-(-len(seq) // ref.ROWS) * ref.ROWS,), np.int32)
        ids[:len(seq)] = seq
        # positions P-1 .. P+CHECK_NEW-2 predict the emitted tokens
        scored = slice(len(p) - 1, len(p) - 1 + CHECK_NEW)
        per_layer = []

        def tap(n, blk):
            z = np.asarray(ref.router_logits(n[scored], blk["router_w"]))
            order = np.argsort(-z, axis=-1)[:, k - 1:k + 1]
            zk, zn = np.take_along_axis(z, order, -1).T
            held = ((order >= first) & (order < first + count)).any(-1)
            per_layer.append(np.where(held, (zk - zn) / np.abs(zk), np.inf))

        hid = ref.hidden(
            w, ids, windows=windows, experts_held=sz["experts_held"],
            n_head=sz["heads"], n_kv_head=sz["kv_heads"],
            head_dim=sz["head_dim"], eps=sz["eps"], theta=sz["theta"],
            top_k=k, tap=tap)
        rows.append(hid[scored])
        margins.append(np.min(per_layer, axis=0))
    lg = ref.head(jnp.stack(rows), w["lnf_g"], w["wte"], eps=sz["eps"],
                  logit_scale=ctx.config["logit_scale"])
    return np.asarray(lg), np.stack(margins)


def judge(lg, emitted, margin, tol: dict) -> dict:
    """``emitted`` tokens against the reference's logits ``lg``.  Left out
    of the shortfall limits are positions where the router is tied: the
    gap between its ``top_k``-th and next logit is under
    ``router_tie_margin`` of their size, one of the two is held, and so
    two computations that differ by a rounding pick different experts and
    that position's logits move by a held expert's weighted output,
    whatever the precision.  They count in the argmax share like any
    other.  A tie is told from the reference's own router logits, not from
    anything the program did, and it comes in runs: the first layer's
    router sees the token's embedding and nothing else, and a greedy
    request of a seeded model often settles on one token, so a request
    sits on a tie at most of its positions or at few (up to 96 % of one,
    29 % of a set).  ``router_tie_share_max`` therefore only keeps one
    request's worth of positions under the two shortfall limits."""
    short = lg.max(-1) - np.take_along_axis(lg, emitted[..., None], -1)[..., 0]
    tied = margin < tol["router_tie_margin"]
    kept = short[~tied]
    got = {"argmax_share": float((lg.argmax(-1) == emitted).mean()),
           "shortfall_mean": float(kept.mean()),
           "shortfall_max": float(kept.max()),
           "tie_share": float(tied.mean()),
           "tied_shortfall_max": float(short[tied].max()) if tied.any()
           else 0.0}
    top = np.argsort(-short, axis=None)[:3]
    got["worst"] = [(round(float(short.flat[i]), 4),
                     round(float(min(margin.flat[i], 9.0)), 5)) for i in top]
    got["ok"] = bool(
        got["argmax_share"] >= tol["serve_argmax_share_min"]
        and got["shortfall_mean"] <= tol["serve_logit_shortfall_mean"]
        and got["shortfall_max"] <= tol["serve_logit_shortfall_max"]
        and got["tie_share"] <= tol["router_tie_share_max"])
    return got


def reference_check(server: Server, ctx) -> dict:
    """Greedy requests through chunked prefill and paged decode; the
    reference runs teacher-forced along the tokens the engine emitted.
    Limits (the configuration's ``check`` gives each its two readings):
    the mean and the largest shortfall of an emitted token's reference
    logit under the reference's maximum, router ties left out (``judge``),
    and the share of emitted tokens that are the reference's argmax."""
    prompts, fins = emitted_tokens(server, ctx)
    for i, fin in enumerate(fins):
        if not fin.ok or len(fin.tokens) != CHECK_NEW:
            ctx.log(f"serve check: request {i} ended {fin.finish_reason!r} "
                    f"with {len(fin.tokens)} tokens")
            return {"reference_logits": False}
    emitted = np.stack([np.asarray(f.tokens) for f in fins])
    lg, margin = reference_logits(server, ctx, prompts, emitted)
    tol = ctx.config["check"]
    got = judge(lg, emitted, margin, tol)
    server.checked = dict(prompts=prompts, emitted=emitted, logits=lg,
                          margin=margin)
    ctx.log(f"serve check: reference-logit shortfall mean "
            f"{got['shortfall_mean']:.4f} max {got['shortfall_max']:.4f} "
            f"(tol mean {tol['serve_logit_shortfall_mean']}, max "
            f"{tol['serve_logit_shortfall_max']}) outside the "
            f"{got['tie_share']:.1%} of positions where the router is tied "
            f"(margin under {tol['router_tie_margin']}; at most "
            f"{tol['router_tie_share_max']:.0%}; largest shortfall there "
            f"{got['tied_shortfall_max']:.4f}); {got['argmax_share']:.1%} of "
            f"the emitted tokens are the reference's argmax (at least "
            f"{tol['serve_argmax_share_min']:.0%}); top logit "
            f"{lg.max(-1).mean():.2f}, logit std {lg.std():.3f}; the three "
            f"largest shortfalls with their router margins "
            f"{got['worst']}")
    return {"reference_logits": got["ok"]}


def run(ctx) -> dict:
    server = Server(ctx)
    checks = reference_check(server, ctx)
    eng = server.engine
    paths = eng.attention_paths()
    checks["compiled_kernels"] = all(v == "kernel" for v in paths.values())
    st = eng.stats
    ctx.log(f"serve: attention paths {paths}; after the check "
            f"{st['prefill_traces']} prefill and {st['decode_traces']} decode "
            f"programs traced; {st.get('window_pages_recycled')} window "
            f"pages recycled")
    ctx.mark("check_and_warm")
    server.weights = server.checked = None
    server.token_times.clear()
    schedule = make_schedule(ctx, float(ctx.cell["rate_rps"]), ctx.seconds)
    obs = drive(server, ctx, schedule, ctx.seconds)
    out = score(server, ctx, obs)
    out["checks"].update(checks)
    lo, hi = obs["origin"], obs["origin"] + obs["seconds"]
    before = [v for t, v in server.window_pages if t < lo][-1]
    ring = getattr(eng, "ring", None)
    out["run"].update(
        window_pages_peak=max([before] + [v for t, v in server.window_pages
                                          if lo <= t < hi]),
        window_pages=None if ring is None else ring.num_pages - 1,
        decode_rows=ctx.config["engine"]["max_slots"],
        chunk_tokens=eng.chunk_tokens, moe_sizes=server.sz)
    r, v = out["run"], out["values"]
    ctx.log(f"serve: {out['attempted']} scored, {out['failed']} failed, "
            f"{r['done_requests']} completed in the window "
            f"({r['completed_tokens_per_s']:.0f} tokens/s), served "
            f"{v['serve_tokens_per_s']} tokens/s, ttft p50 "
            f"{r['ttft_p50_ms']} p95 {r['ttft_p95_ms']} ms, gap p95 "
            f"{v['tbt_p95_ms']} ms over {r['n_gaps']} gaps ({r['gap_ms']}), "
            f"backlog "
            f"{r['backlog_third']:.1f} a third in and {r['backlog_end']:.1f} "
            f"at the end, drained {r['drain_s']:.1f} s after it; pages peak "
            f"{r['pages_peak']} full, {r['window_pages_peak']} window")
    return out


# ---------------------------------------------------------------------------
# the check's second reading: the reference in a precision below
# ---------------------------------------------------------------------------

def _int8(x):
    """Symmetric 8-bit rounding with one scale a column: the mildest there
    is (what ``ops/quant_ops.py::quantize_per_channel`` does to a
    projection's weights)."""
    x = x.astype(ref.F32)
    if x.ndim < 2:
        return x
    top = jnp.max(jnp.abs(x), axis=-2, keepdims=True)
    scale = jnp.where(top > 0, top, 1.0) / 127.0
    return jnp.round(x / scale) * scale


def precision_study(server: Server, ctx, kinds: tuple[str, ...]) -> dict:
    """After ``reference_check``: for each kind (``int8`` or a dtype's
    name) the reference is run again along the same tokens with every
    weight rounded to it at use, and the tokens IT would emit (its argmax)
    go through ``judge`` against the unrounded reference, as the
    program's did: what a system of that precision reads by the cell's
    limits."""
    c, tol = server.checked, ctx.config["check"]
    out = {"program": judge(c["logits"], c["emitted"], c["margin"], tol)}
    widen = ref._w
    try:
        for kind in kinds:
            ref._w = _int8 if kind == "int8" else (
                lambda x, dt=jnp.dtype(kind): x.astype(dt).astype(ref.F32))
            jax.clear_caches()
            lo, _ = reference_logits(server, ctx, c["prompts"], c["emitted"])
            out[kind] = dict(
                judge(c["logits"], lo.argmax(-1), c["margin"], tol),
                logit_rms_err=float(np.sqrt(((lo - c["logits"]) ** 2).mean())))
    finally:
        ref._w = widen
        jax.clear_caches()
    return out


def main() -> int:
    """``python -m benchmarks.jobs.serve_cohere2 --workload W --seed N
    [--prompt-seeds A,B] [--round int8,float8_e4m3fn]``: the readings
    behind the configuration's ``check`` limits, one JSON line a set of
    check prompts (the program's, and each rounded reference's), with the
    shortfall and the router margin of every position written to
    ``chiprun_out/``."""
    import argparse

    from benchmarks import run, weights

    t_process = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt-seeds", default="")
    ap.add_argument("--round", default="int8")
    args = ap.parse_args()
    cell = run.load_json(os.path.join(run.HERE, "workloads",
                                      f"{args.workload}.json"))
    config = run.load_json(os.path.join(run.HERE, "configs",
                                        f"{cell['config']}.json"))
    run.require_tpu(cell["chips"])
    ctx = run.Context(
        cell=cell, config=config, traffic={}, sizes=weights.sizes(config),
        seed=args.seed, seconds=0.0, tracer=run.WindowTracer(False, "", 0.0),
        t_process=t_process, spans=SPANS)
    sut.configure_compile_cache()
    server = Server(ctx)
    seeds = [args.seed] + [int(x) for x in args.prompt_seeds.split(",") if x]
    checked = []
    for seed in seeds:             # the weights stay; the prompts change
        each = dataclasses.replace(ctx, seed=seed)
        ok = reference_check(server, each)
        checked.append((each, server.checked, ok))
    out_dir = os.path.join(run.CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    kinds = tuple(k for k in args.round.split(",") if k)
    for each, c, ok in checked:
        server.checked = c
        lg, em = c["logits"], c["emitted"]
        short = lg.max(-1) - np.take_along_axis(lg, em[..., None], -1)[..., 0]
        with open(os.path.join(
                out_dir, f"check_{args.seed}_{each.seed}.json"), "w") as f:
            json.dump({"shortfall": short.tolist(),
                       "margin": np.where(np.isfinite(c["margin"]),
                                          c["margin"], 1e9).tolist()}, f)
        print(json.dumps({"weights_seed": args.seed, "prompt_seed": each.seed,
                          "checks": ok,
                          **precision_study(server, each, kinds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
