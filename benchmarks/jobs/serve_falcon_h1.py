"""The serving job for a ``falcon_h1`` stage: ``jobs/serve.py``'s open loop,
drive and score, against an engine that serves that model.

Its own: how the model is built (the seeded tree of ``weights_falcon_h1``
is adopted as it is, so the weights exist once; a program without the model
fails before any weight is made) and the reference check
(``reference/falcon_h1_ref.py``): the five prompts of ``jobs/serve.py`` one
at a time, so that every chunk bucket is compiled and ONE slot is taken
five times (a recurrent state that is not zeroed for its next tenant
fails), then two prompts of twelve and of four chunks in ONE batch (the
state is carried from chunk to chunk, two slots advance by different
numbers of valid rows in one step, and one decodes while the other still
prefills), then as many short prompts as the engine has slots in ONE batch
(the decode program at the window's occupancy, lanes live, dead and still
prefilling side by side, the highest slots among those compared).  Beside
the emitted tokens the check compares the recurrent state each request
leaves in its slot with the reference's, which is what tells a state kept
in fewer bits than the configuration states.  Cell and configuration keys
are ``jobs/serve.py``'s.
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

from benchmarks import sut, weights_falcon_h1
from benchmarks.jobs import serve
# run.py reads SPANS; knee_sweep.py drives any serving job by these names
from benchmarks.jobs.serve import SPANS, drive, make_schedule, score  # noqa: F401,E501
from benchmarks.reference import falcon_h1_ref as ref

#: ``jobs/serve.py``'s five (their last chunks fall in the five chunk
#: buckets), one at a time; the engine's ``max_seq_len`` caps every length
#: at tiny sizes
SINGLES = serve.CHECK_PROMPTS
#: then twelve chunks against four in one batch
PAIR = (1531, 397)
#: then the crowd: as many prompts as the engine has slots, of lengths drawn
#: in this range, in one batch, so that the decode program runs at the
#: occupancy the window gives it; of them the first two slots' (used
#: before), two in the middle and the last two are compared
CROWD_TOKENS = (8, 160)
CHECK_NEW = 48
#: what ``precision_study`` can narrow: every weight at use, or the
#: recurrent state as it is carried from row to row
STATE_PREFIX = "state_"


def multipliers(config: dict) -> dict:
    """The reference's ``mult`` from the configuration's published keys."""
    return dict(
        embedding=config["embedding_multiplier"],
        attention_in=config["attention_in_multiplier"],
        attention_out=config["attention_out_multiplier"],
        key=config["key_multiplier"], ssm_in=config["ssm_in_multiplier"],
        ssm_out=config["ssm_out_multiplier"],
        ssm=tuple(config["ssm_multipliers"]),
        mlp=tuple(config["mlp_multipliers"]),
        lm_head=config["lm_head_multiplier"])


def model_config(sz: dict, config: dict):
    from paddle_tpu.models import FalconH1Config

    m = multipliers(config)
    assert sz["d_ssm"] == sz["ssm_heads"] * sz["ssm_head_dim"]
    return FalconH1Config(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], num_heads=sz["heads"],
        num_kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        intermediate_size=sz["ffn"], mamba_d_ssm=sz["d_ssm"],
        mamba_n_heads=sz["ssm_heads"], mamba_d_head=sz["ssm_head_dim"],
        mamba_n_groups=sz["ssm_groups"], mamba_d_state=sz["d_state"],
        mamba_d_conv=sz["d_conv"], mamba_chunk_size=sz["ssm_chunk"],
        rope_theta=sz["theta"], rms_norm_eps=sz["eps"],
        embedding_multiplier=m["embedding"],
        attention_in_multiplier=m["attention_in"],
        attention_out_multiplier=m["attention_out"],
        key_multiplier=m["key"], ssm_in_multiplier=m["ssm_in"],
        ssm_out_multiplier=m["ssm_out"], ssm_multipliers=m["ssm"],
        mlp_multipliers=m["mlp"], lm_head_multiplier=m["lm_head"],
        max_seq_len=config["max_position_embeddings"], dtype=config["dtype"],
        ssm_state_dtype=config["ssm_state_dtype"])


class Server(serve.Server):
    def __init__(self, ctx):
        # a program without the model fails here, before ten gigabytes of
        # weights are made for it
        from paddle_tpu.models import FalconH1ForCausalLM

        cfg = ctx.config
        self.sz = weights_falcon_h1.sizes(cfg)
        self.weights = weights_falcon_h1.make(cfg, ctx.seed, cfg["dtype"])
        model = FalconH1ForCausalLM(model_config(self.sz, cfg),
                                    weights=self.weights)
        ctx.mark("weights")
        self.token_times: dict[int, list[float]] = {}
        #: what ``reference_check`` compared, for ``precision_study``
        self.checked: dict | None = None
        self.engine = sut.build_engine(
            model, sizes=cfg["engine"], seed=ctx.seed, on_token=self._on_token)
        ctx.mark("engine")


def check_batches(server: Server, ctx) -> list[tuple[list, set[int]]]:
    """The check's batches in order: (prompts, the slots whose requests
    are compared with the reference)."""
    eng = ctx.config["engine"]
    cap, slots = eng["max_seq_len"] - CHECK_NEW, eng["max_slots"]
    rng = np.random.default_rng([ctx.seed, 0xC4EC])

    def draw(n):
        return rng.integers(0, server.sz["vocab"],
                            min(int(n), cap)).astype(np.int32)

    few = [[draw(n)] for n in SINGLES] + [[draw(n) for n in PAIR]]
    crowd = [draw(n) for n in rng.integers(
        CROWD_TOKENS[0], CROWD_TOKENS[1] + 1, slots)]
    ends = {0, 1, slots // 2 - 1, slots // 2, slots - 2, slots - 1}
    return [(b, set(range(slots))) for b in few] + [(crowd, ends)]


def run_batch(server: Server, prompts: list, compared: set[int]) -> dict:
    """``prompts`` through the engine together, greedy, ``CHECK_NEW`` tokens
    each.  ``fins``: every finished request; ``picks``: the requests that
    held a slot of ``compared``, in the slots' order, with ``slots`` and
    ``states``, the recurrent state (layers, H, P, N) each left in its
    slot, read as the request ends (the slot's next tenant zeroes it);
    ``lanes``: the most that decoded in one step."""
    eng = server.engine
    rids = [eng.add_request(p, CHECK_NEW) for p in prompts]
    slot_of, done, states, lanes = {}, {}, {}, 0
    while eng.has_work:
        for slot, st in enumerate(eng._slots):
            if st is not None:
                slot_of[st.request.rid] = slot
        lanes = max(lanes, sum(st is not None and st.started
                               for st in eng._slots))
        for fin in eng.step():
            done[fin.rid] = fin
            if slot_of.get(fin.rid) in compared:
                states[fin.rid] = np.asarray(
                    eng.slab.buffers["ssm"][:, slot_of[fin.rid]])
    picks = sorted((i for i, r in enumerate(rids) if r in states),
                   key=lambda i: slot_of[rids[i]])
    return dict(fins=[done[r] for r in rids], picks=picks,
                slots=[slot_of[rids[i]] for i in picks],
                states=[states[rids[i]] for i in picks], lanes=lanes)


def reference_along(server: Server, ctx, prompts, emitted):
    """The reference teacher-forced along ``emitted`` (n, CHECK_NEW): its
    logits (n, CHECK_NEW, vocab) at the positions that predict those
    tokens, and a request the recurrent state (layers, H, P, N) after the
    rows the engine's has folded in: the prompt and every emitted token
    but the last."""
    sz, w = server.sz, server.weights
    mult = multipliers(ctx.config)
    logits, states = [], []
    for p, toks in zip(prompts, emitted):
        # padded on the right to whole blocks of rows: under a causal mask
        # the padding changes no earlier position, the recurrence is told
        # where the sequence ends, and lengths share shapes
        seq = np.concatenate([p, toks[:-1]])
        ids = np.zeros((-(-len(seq) // ref.ROWS) * ref.ROWS,), np.int32)
        ids[:len(seq)] = seq
        hid, hs = ref.hidden(
            w, ids, rows=len(seq), mult=mult, n_head=sz["heads"],
            n_kv_head=sz["kv_heads"], head_dim=sz["head_dim"],
            theta=sz["theta"], eps=sz["eps"], d_ssm=sz["d_ssm"],
            ssm_heads=sz["ssm_heads"], ssm_groups=sz["ssm_groups"],
            d_state=sz["d_state"])
        # positions P-1 .. P+CHECK_NEW-2 predict the emitted tokens; a
        # request at a time, so that 48 rows of the vocabulary are all
        # that lies on the device
        logits.append(np.asarray(ref.head(
            hid[len(p) - 1:len(p) - 1 + CHECK_NEW], w["lnf_g"], w["lm_head"],
            eps=sz["eps"], lm_head_mult=mult["lm_head"])))
        states.append(np.stack([np.asarray(h) for h in hs]))
    return np.stack(logits), states


def state_error(states: list, want: list) -> list[float]:
    """A request: the distance of its whole recurrent state, every layer
    and head, from the reference's, as a share of the reference's norm."""
    return [float(np.sqrt(np.square(s.astype(np.float64) - r).sum()
                          / np.square(r.astype(np.float64)).sum()))
            for s, r in zip(states, want)]


def judge(lg, emitted, state_err: list[float], tol: dict) -> dict:
    """``emitted`` tokens against the reference's logits ``lg``: the mean
    and the largest shortfall of an emitted token's reference logit under
    the reference's maximum at its position, and the share of emitted
    tokens that are the reference's argmax; and ``state_err``, a request
    the distance of the recurrent state it left from the reference's
    (``state_error``): the largest."""
    short = lg.max(-1) - np.take_along_axis(lg, emitted[..., None], -1)[..., 0]
    got = {"argmax_share": float((lg.argmax(-1) == emitted).mean()),
           "shortfall_mean": float(short.mean()),
           "shortfall_max": float(short.max()),
           "shortfall_max_by_request": [round(float(v), 6)
                                        for v in short.max(-1)],
           "state_err_max": max(state_err),
           "state_err_by_request": [round(v, 6) for v in state_err]}
    got["ok"] = bool(
        got["argmax_share"] >= tol["serve_argmax_share_min"]
        and got["shortfall_mean"] <= tol["serve_logit_shortfall_mean"]
        and got["shortfall_max"] <= tol["serve_logit_shortfall_max"]
        and got["state_err_max"] <= tol["serve_state_rel_err_max"])
    return got


def slab_as_stated(server: Server, ctx) -> bool:
    """The slab keeps the state in the type the configuration states, and
    holds the bytes that type gives ``max_slots`` slots: a slab in fewer
    bits is another configuration, whatever the comparison reads."""
    cfg, sz, slab = ctx.config, server.sz, server.engine.slab
    conv = sz["d_ssm"] + 2 * sz["ssm_groups"] * sz["d_state"]
    want = sz["layers"] * cfg["engine"]["max_slots"] * (
        sz["ssm_heads"] * sz["ssm_head_dim"] * sz["d_state"]
        * jnp.dtype(cfg["ssm_state_dtype"]).itemsize
        + (sz["d_conv"] - 1) * conv * jnp.dtype(cfg["dtype"]).itemsize)
    ok = (slab.buffers["ssm"].dtype == jnp.dtype(cfg["ssm_state_dtype"])
          and slab.hbm_bytes() == want)
    if not ok:
        ctx.log(f"serve check: the slab keeps its state in "
                f"{slab.buffers['ssm'].dtype} and holds {slab.hbm_bytes()} "
                f"bytes; the configuration states {cfg['ssm_state_dtype']} "
                f"and {want} bytes")
    return bool(ok)


def reference_check(server: Server, ctx) -> dict:
    """Greedy requests through chunked prefill and paged decode; the
    reference runs teacher-forced along the tokens the engine emitted.
    Limits (the configuration's ``check`` gives each its two readings):
    the mean and the largest shortfall of an emitted token's reference
    logit under the reference's maximum, the share of emitted tokens that
    are the reference's argmax, and the distance of the recurrent state a
    request leaves in its slot from the reference's."""
    checks = {"state_slab": slab_as_stated(server, ctx),
              "reference_logits": False}
    prompts, emitted, states, slots, lanes = [], [], [], [], 0
    for batch, compared in check_batches(server, ctx):
        ran = run_batch(server, batch, compared)
        for i, fin in enumerate(ran["fins"]):
            if not fin.ok or len(fin.tokens) != CHECK_NEW:
                ctx.log(f"serve check: request {i} of a batch of "
                        f"{len(batch)} ended {fin.finish_reason!r} with "
                        f"{len(fin.tokens)} tokens")
                return checks
        prompts += [batch[i] for i in ran["picks"]]
        emitted += [np.asarray(ran["fins"][i].tokens) for i in ran["picks"]]
        states += ran["states"]
        slots += ran["slots"]
        lanes = max(lanes, ran["lanes"])
    emitted = np.stack(emitted)
    lg, want = reference_along(server, ctx, prompts, emitted)
    tol = ctx.config["check"]
    got = judge(lg, emitted, state_error(states, want), tol)
    server.checked = dict(prompts=prompts, emitted=emitted, logits=lg,
                          states=states, ref_states=want, slots=slots)
    ctx.log(f"serve check: {len(prompts)} requests compared (slots {slots}; "
            f"up to {lanes} lanes decoded together): reference-logit "
            f"shortfall mean {got['shortfall_mean']:.6f} max "
            f"{got['shortfall_max']:.6f} "
            f"(tol mean {tol['serve_logit_shortfall_mean']}, max "
            f"{tol['serve_logit_shortfall_max']}; by request "
            f"{got['shortfall_max_by_request']}); "
            f"{got['argmax_share']:.1%} of the emitted tokens are the "
            f"reference's argmax (at least "
            f"{tol['serve_argmax_share_min']:.1%}); recurrent state off the "
            f"reference's by at most {got['state_err_max']:.6f} of its norm "
            f"(tol {tol['serve_state_rel_err_max']}; by request "
            f"{got['state_err_by_request']}); top logit "
            f"{lg.max(-1).mean():.4f}, logit std {lg.std():.5f}")
    checks["reference_logits"] = got["ok"]
    return checks


#: an engine step that takes this long is kept with its phases
SLOW_STEP_S = 0.25


def watch_steps(eng) -> list[dict]:
    """Keep, of every ``eng.step()`` from here on that takes
    ``SLOW_STEP_S`` or more, the engine's own times for it (ms: the step,
    its admissions, its chunk dispatches, its decode dispatch and, inside
    that, the wait for the device's tokens) and when it ended.  Most runs
    have none; a stalled window (``PERF.md`` section 7 (5)) has one, and
    its phases tell the host's loop, a dispatch and the device apart."""
    slow, step = [], eng.step

    def watched():
        out = step()
        st = eng.stats
        if st["last_step_s"] >= SLOW_STEP_S:
            slow.append(dict(
                {k: round(1e3 * st[f"last_{k}_s"], 1) for k in
                 ("step", "admit", "prefill", "decode", "decode_sync")},
                at=time.perf_counter()))
        return out

    eng.step = watched
    return slow


def longest_steps(obs: dict, n: int = 3) -> list[tuple[float, float]]:
    """The ``n`` longest times from the end of one engine step to the end
    of the next, lead-in and drain included: (ms, seconds on the window's
    clock at which it ended).  A run that reads under its neighbours shows
    here whether one step stalled or all were slow; a long one that
    ``watch_steps`` did not keep was spent outside the engine."""
    ends = [t for t, _ in obs["depth"]]
    gaps = sorted(((b - a, b) for a, b in zip(ends, ends[1:])),
                  reverse=True)[:n]
    return [(round(1e3 * g, 1), round(at, 2)) for g, at in gaps]


def run(ctx) -> dict:
    server = Server(ctx)
    checks = reference_check(server, ctx)
    eng = server.engine
    paths = eng.attention_paths()
    checks["compiled_kernels"] = all(v == "kernel" for v in paths.values())
    st = eng.stats
    ctx.log(f"serve: attention and state paths {paths}; after the check "
            f"{st['prefill_traces']} prefill and {st['decode_traces']} decode "
            f"programs traced; {st.get('state_resets')} state resets, slab "
            f"{st.get('state_slab_bytes')} bytes")
    ctx.mark("check_and_warm")
    server.weights = server.checked = None
    server.token_times.clear()
    schedule = make_schedule(ctx, float(ctx.cell["rate_rps"]), ctx.seconds)
    slow = watch_steps(eng)
    obs = drive(server, ctx, schedule, ctx.seconds)
    out = score(server, ctx, obs)
    out["checks"].update(checks)
    for s in slow:                           # on the window's clock
        s["at"] = round(s["at"] - obs["origin"], 2)
    out["run"].update(ssm_sizes=server.sz, longest_steps=longest_steps(obs),
                      slow_steps=slow)
    r, v = out["run"], out["values"]
    ctx.log(f"serve: {out['attempted']} scored, {out['failed']} failed, "
            f"{r['done_requests']} completed in the window "
            f"({r['completed_tokens_per_s']:.0f} tokens/s), served "
            f"{v['serve_tokens_per_s']} tokens/s, ttft p50 "
            f"{r['ttft_p50_ms']} p95 {r['ttft_p95_ms']} ms, gap p95 "
            f"{v['tbt_p95_ms']} ms over {r['n_gaps']} gaps ({r['gap_ms']}), "
            f"backlog {r['backlog_third']:.1f} a third in and "
            f"{r['backlog_end']:.1f} at the end, drained {r['drain_s']:.1f} s "
            f"after it; pages peak {r['pages_peak']}; longest steps (ms, "
            f"ending at s of the window's clock) {r['longest_steps']}; "
            f"steps of {SLOW_STEP_S} s or more {r['slow_steps']}")
    return out


# ---------------------------------------------------------------------------
# the check's second reading: the reference in a precision below
# ---------------------------------------------------------------------------

def precision_study(server: Server, ctx, kinds: tuple[str, ...]) -> dict:
    """After ``reference_check``: for each kind the reference is run again
    along the same tokens, narrowed, and the tokens IT would emit (its
    argmax) and the states IT leaves go through ``judge`` against the
    unnarrowed reference, as the program's did: what a system of that
    precision reads by the cell's limits.  A dtype's name rounds every
    weight to it at use; ``state_<dtype>`` keeps the recurrent state in it
    from row to row."""
    c, tol = server.checked, ctx.config["check"]
    out = {"program": judge(c["logits"], c["emitted"],
                            state_error(c["states"], c["ref_states"]), tol)}
    widen, carry = ref._w, ref._state
    try:
        for kind in kinds:
            ref._w, ref._state = widen, carry
            if kind.startswith(STATE_PREFIX):
                # reduce_precision, not a cast there and back: the TPU
                # compiler drops such a pair as excess precision
                fi = jnp.finfo(jnp.dtype(kind[len(STATE_PREFIX):]))
                ref._state = lambda h, fi=fi: jax.lax.reduce_precision(
                    h, fi.nexp, fi.nmant)
            else:
                ref._w = lambda x, dt=jnp.dtype(kind): \
                    x.astype(dt).astype(ref.F32)
            jax.clear_caches()
            lo, states = reference_along(server, ctx, c["prompts"],
                                         c["emitted"])
            out[kind] = dict(
                judge(c["logits"], lo.argmax(-1),
                      state_error(states, c["ref_states"]), tol),
                logit_rms_err=float(np.sqrt(((lo - c["logits"]) ** 2).mean())))
    finally:
        ref._w, ref._state = widen, carry
        jax.clear_caches()
    return out


def main() -> int:
    """``python -m benchmarks.jobs.serve_falcon_h1 --workload W --seed N
    [--prompt-seeds A,B] [--round float8_e4m3fn,state_bfloat16]``: the
    readings behind the configuration's ``check`` limits, one JSON line a
    set of check prompts (the program's, and each narrowed reference's),
    with the shortfall of every position and the program's state error of
    every head written to ``chiprun_out/``."""
    import argparse

    from benchmarks import run as harness
    from benchmarks import weights

    t_process = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt-seeds", default="")
    ap.add_argument("--round", default="float8_e4m3fn,state_bfloat16")
    args = ap.parse_args()
    cell = harness.load_json(os.path.join(harness.HERE, "workloads",
                                          f"{args.workload}.json"))
    config = harness.load_json(os.path.join(harness.HERE, "configs",
                                            f"{cell['config']}.json"))
    harness.require_tpu(cell["chips"])
    ctx = harness.Context(
        cell=cell, config=config, traffic={}, sizes=weights.sizes(config),
        seed=args.seed, seconds=0.0,
        tracer=harness.WindowTracer(False, "", 0.0), t_process=t_process,
        spans=SPANS)
    sut.configure_compile_cache()
    server = Server(ctx)
    seeds = [args.seed] + [int(x) for x in args.prompt_seeds.split(",") if x]
    out_dir = os.path.join(harness.CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    kinds = tuple(k for k in args.round.split(",") if k)
    for seed in seeds:             # the weights stay; the prompts change
        each = dataclasses.replace(ctx, seed=seed)
        ok = reference_check(server, each)
        c = server.checked
        lg, em = c["logits"], c["emitted"]
        short = lg.max(-1) - np.take_along_axis(lg, em[..., None], -1)[..., 0]
        # request, layer, head: the state's distance from the reference's
        # as a share of the reference's norm
        by_head = [np.sqrt(np.square(s.astype(np.float64) - r).sum((-2, -1))
                           / np.square(r.astype(np.float64)).sum((-2, -1)))
                   for s, r in zip(c["states"], c["ref_states"])]
        with open(os.path.join(
                out_dir, f"h1check_{args.seed}_{seed}.json"), "w") as f:
            json.dump({"shortfall": short.tolist(), "slots": c["slots"],
                       "state_err_by_layer_head": np.stack(by_head).tolist()},
                      f)
        print(json.dumps({"weights_seed": args.seed, "prompt_seed": seed,
                          "checks": ok,
                          **precision_study(server, each, kinds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
