"""Falcon-H1 (``falcon_h1``) on the normal path, at tiny widths that keep
the model's ratios (10 query heads over 2 KV heads: a GQA group of 5; 4
mixer heads in 2 groups), float32, CPU, seeded weights:

* the eager forward against ``reference_falcon_h1`` (a byte-identical copy
  of ``benchmarks/reference/falcon_h1_ref.py``), logits to float32 rounding;
* chunked prefill then paged decode through ``ServingEngine`` against the
  reference's full forward, logits-level, with every kernel pinned in
  interpret mode and on the jnp paths;
* the two kernels of the recurrence against the sequential recurrence:
  every chunk bucket, a carried state, padding rows, dead lanes;
* the state slab's lifecycle: a reused slot starts from zero, a preempted
  request recomputes to the same tokens, two slots advance by different
  numbers of rows in one step, the invariants hold after every step;
* what a model with recurrent state refuses, by a named error, and its
  counters: present for it, absent for a model without state.
"""

import filecmp
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import reference_falcon_h1 as ref
from paddle_tpu.kernels import ssd
from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import MultiGroupUnsupported

HERE = os.path.dirname(os.path.abspath(__file__))
SIZES = dict(n_head=10, n_kv_head=2, head_dim=16, theta=1e11, eps=1e-5,
             d_ssm=64, ssm_heads=4, ssm_groups=2, d_state=16)
ENGINE = dict(max_slots=3, page_size=16, max_seq_len=256, chunk_tokens=16)


def _cfg(**kw):
    return FalconH1Config(**dict(dict(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=10,
        num_kv_heads=2, head_dim=16, intermediate_size=128, mamba_d_ssm=64,
        mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=2, mamba_d_state=16,
        embedding_multiplier=5.66, attention_out_multiplier=0.5,
        key_multiplier=0.3, ssm_in_multiplier=0.25, ssm_out_multiplier=0.5,
        ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.35),
        mlp_multipliers=(0.18, 0.3), lm_head_multiplier=0.5,
        max_seq_len=512, dtype="float32"), **kw))


@pytest.fixture(scope="module")
def model():
    return FalconH1ForCausalLM(_cfg(), seed=3)


def _mult(cfg):
    return dict(
        embedding=cfg.embedding_multiplier,
        attention_in=cfg.attention_in_multiplier,
        attention_out=cfg.attention_out_multiplier, key=cfg.key_multiplier,
        ssm_in=cfg.ssm_in_multiplier, ssm_out=cfg.ssm_out_multiplier,
        ssm=cfg.ssm_multipliers, mlp=cfg.mlp_multipliers,
        lm_head=cfg.lm_head_multiplier)


def _ref_logits(model, ids):
    return np.asarray(ref.logits(model.decoder_params(), ids,
                                 mult=_mult(model.cfg), **SIZES))


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, n).astype(np.int32) for n in lengths]


def _serve(model, prompts, new, check=False, **kw):
    """(engine, tokens of each request) of one drained run."""
    eng = ServingEngine(model, **dict(ENGINE, **kw))
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = {}
    while eng.has_work:
        for fin in eng.step():
            done[fin.rid] = fin
        if check:
            eng.check_invariants()
    return eng, [np.asarray(done[r].tokens) for r in rids]


def test_the_two_reference_files_are_byte_equal():
    assert filecmp.cmp(
        os.path.join(HERE, "reference_falcon_h1.py"),
        os.path.join(os.path.dirname(HERE), "benchmarks", "reference",
                     "falcon_h1_ref.py"), shallow=False)


def test_the_model_is_described_by_data(model):
    spec, = set(model.layer_specs())
    assert (spec.norm, spec.position, spec.mlp) == ("rms", "rope_half",
                                                    "gated_silu")
    assert not spec.parallel and spec.window is None and spec.moe is None
    m = spec.ssm
    assert (m.d_ssm, m.n_heads, m.n_groups, m.d_state, m.conv_dim,
            m.in_dim) == (64, 4, 2, 16, 128, 196)
    assert m.state_dtype == "float32" and m.mup == (0.35, 0.25, 0.18, 0.5,
                                                    0.35)
    assert spec.mup.key == 0.3 and spec.mup.head == 0.5
    p = model.decoder_params()
    assert p["lm_head"].shape == p["wte"].shape == (512, 64)
    assert p["lm_head"] is not p["wte"]


def test_eager_forward_matches_the_reference(model):
    ids = _prompts(0, (50,))[0]
    got = np.asarray(model.logits(ids[None]))[0]
    want = _ref_logits(model, ids)
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("paths", ["jnp", "kernel"])
def test_prefill_and_decode_through_the_engine_match_the_reference(
        model, paths):
    """Three prompts in one batch (four chunks, two, one: the state is
    carried across chunks and the slots advance by different numbers of
    valid rows a step), then 8 decode steps: the reference, teacher-forced
    along the emitted tokens, has each of them as its argmax, by a
    margin."""
    prompts, new = _prompts(1, (50, 21, 7)), (8, 8, 8)
    eng, toks = _serve(model, prompts, new, check=True,
                       use_paged_kernel=paths == "kernel")
    want = "kernel" if paths == "kernel" else "reference"
    assert set(eng.attention_paths().values()) == {want}
    assert set(eng.attention_paths()) == {"decode", "prefill", "ssm_step",
                                          "ssm_scan"}
    for p, t in zip(prompts, toks):
        lg = _ref_logits(model, np.concatenate([p, t[:-1]]))[len(p) - 1:]
        short = lg.max(-1) - np.take_along_axis(lg, t[:, None], -1)[:, 0]
        assert short.max() <= 1e-5, short
    st = eng.stats
    assert st["state_resets"] == 3
    assert st["ssm_scan_rows"] == 2 * (50 + 21 + 7)
    # buckets of 8 and 16 rows: 50 = 16 x 3 + 2, 21 = 16 + 5, 7
    assert st["ssm_scan_row_passes"] == 2 * (56 + 24 + 8)
    assert st["ssm_live_lane_steps"] == 2 * 3 * 7
    walked = st["ssm_live_lane_steps"] if paths == "kernel" else \
        2 * 3 * st["decode_calls"]
    assert st["ssm_lane_steps"] == walked
    assert st["state_slab_bytes"] == eng.slab.hbm_bytes() == 2 * 3 * (
        4 * 16 * 16 * 4 + 3 * 128 * 4)


def _operands(rng, rows, slab_rows=6, h=4, g=2, p=16, n=16):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return (f(slab_rows, h, p, n), f(rows, h, p),
            jnp.asarray(rng.uniform(1e-3, 1e-1, (rows, h)), jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32),
            f(rows, g, n), f(rows, g, n), f(h))


@pytest.mark.parametrize("rows,valid", [(8, 5), (16, 16), (32, 20), (64, 33),
                                        (128, 101)])
def test_chunk_scan_kernel_is_the_sequential_recurrence(rows, valid):
    """Every chunk bucket, from a carried state, with padding rows: the
    valid rows' outputs, the state after the LAST VALID row, and every
    other row of the slab untouched."""
    slab, x, dt, a, b, c, d = _operands(np.random.default_rng(rows), rows)
    dt = dt * (jnp.arange(rows) < valid)[:, None]
    y, out = jax.jit(lambda *args: ssd.ssd_chunk_scan(
        slab, 3, *args, interpret=True))(x, dt, a, b, c, d)
    want_y, want_h = ssd.scan_rows(slab[3], x[:valid], dt[:valid], a,
                                   b[:valid], c[:valid], d)
    np.testing.assert_allclose(y[:valid], want_y, atol=2e-5)
    np.testing.assert_allclose(out[3], want_h, atol=2e-6)
    keep = np.array([0, 1, 2, 4, 5])
    np.testing.assert_array_equal(out[keep], slab[keep])
    # the jnp path is the same function of the same arguments
    y2, out2 = ssd.ssd_chunk_scan_ref(slab, 3, x, dt, a, b, c, d)
    np.testing.assert_allclose(y2[:valid], want_y, atol=1e-6)
    np.testing.assert_allclose(out2[3], want_h, atol=1e-6)


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_state_step_advances_live_lanes_and_leaves_dead_ones(path):
    lanes = 5
    slab, x, dt, a, b, c, d = _operands(np.random.default_rng(7), lanes,
                                        slab_rows=2 * lanes)
    active = jnp.asarray([True, False, True, True, False])
    step = (lambda *args: ssd.ssm_state_step(*args, interpret=True)) \
        if path == "kernel" else ssd.ssm_state_step_ref
    y, out = jax.jit(step)(slab, lanes, x, dt, a, b, c, d, active)
    for i in range(lanes):
        if active[i]:
            want_y, want_h = ssd.scan_rows(
                slab[lanes + i], x[i:i + 1], dt[i:i + 1], a, b[i:i + 1],
                c[i:i + 1], d)
            np.testing.assert_allclose(y[i], want_y[0], atol=2e-6)
            np.testing.assert_allclose(out[lanes + i], want_h, atol=2e-6)
        else:
            assert not np.asarray(y[i]).any()
            np.testing.assert_array_equal(out[lanes + i], slab[lanes + i])
    np.testing.assert_array_equal(out[:lanes], slab[:lanes])   # layer 0


def test_a_reused_slot_starts_from_zero(model):
    """One slot, two tenants: the second emits what it emits on a fresh
    engine, though the first left its state in the slab."""
    first, second = _prompts(2, (40, 23))
    _, alone = _serve(model, [second], (6,), max_slots=1)
    eng, both = _serve(model, [first, second], (6, 6), max_slots=1,
                       check=True)
    assert eng.stats["state_resets"] == 2
    np.testing.assert_array_equal(both[1], alone[0])
    assert np.asarray(eng.slab.buffers["ssm"]).any()    # left as it lies
    assert not eng.slab.advanced.any()


def test_a_preempted_request_recomputes_to_the_same_tokens(model):
    prompts, new = _prompts(5, (8, 16, 30)), (24, 16, 12)
    free, want = _serve(model, prompts, new, page_size=8)
    tight, got = _serve(model, prompts, new, page_size=8, num_pages=9,
                        check=True)
    assert free.stats["preemptions"] == 0
    assert tight.stats["preemptions"] >= 1
    # a re-admission zeroes the state again: nothing of it was saved
    assert tight.stats["state_resets"] == 3 + tight.stats["preemptions"]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert tight.pool.pages_in_use == 0


def test_two_requests_in_one_batch_emit_what_each_emits_alone(model):
    prompts, new = _prompts(6, (45, 9)), (10, 10)
    _, together = _serve(model, prompts, new, check=True)
    for p, n, t in zip(prompts, new, together):
        np.testing.assert_array_equal(_serve(model, [p], (n,))[1][0], t)


@pytest.mark.parametrize("kw", [
    dict(spec_k=2), dict(decode_block=2),
    dict(role="prefill"), dict(role="decode"), dict(kv_bits=8),
    dict(int8=True)], ids=lambda kw: "-".join(f"{k}" for k in kw))
def test_what_recurrent_state_refuses_is_a_named_error(model, kw):
    with pytest.raises(MultiGroupUnsupported, match="recurrent state"):
        ServingEngine(model, **dict(ENGINE, **kw))


def test_recurrent_state_dispatches_ahead_of_the_read(model):
    """What was a refused mode is the step: the slab is advanced by the
    programs in dispatch order and its host mirror at dispatch, so decode
    N+1 goes out before N is read."""
    eng, _ = _serve(model, _prompts(8, (20, 9)), (10, 10), check=True)
    s = eng.stats
    assert s["decode_ahead"] == s["decode_calls"] - 1 > 0
    assert s["decode_sync_first"] == 0


def test_snapshot_handoff_and_prefix_index_are_refused_for_state(model):
    eng = ServingEngine(model, prefix_cache=True, **ENGINE)
    assert eng.pool.prefix is None
    assert eng.stats["prefix_index_refused"] == 1
    with pytest.raises(MultiGroupUnsupported, match="snapshot"):
        eng.snapshot()
    with pytest.raises(MultiGroupUnsupported, match="handoff"):
        eng.ingest_handoff({})


def test_a_chunk_wider_than_the_scan_chunk_is_refused():
    small = FalconH1ForCausalLM(_cfg(mamba_chunk_size=8), seed=0)
    with pytest.raises(ValueError, match="scan chunk"):
        ServingEngine(small, **ENGINE)


def test_the_slab_check_holds_the_state_to_the_slots_positions(model):
    eng = ServingEngine(model, **ENGINE)
    eng.add_request(_prompts(8, (40,))[0], 4)
    eng.step()
    eng.check_invariants()
    slot = next(i for i, s in enumerate(eng._slots) if s is not None)
    eng.slab.advanced[slot] += 1
    with pytest.raises(AssertionError, match="recurrent state advanced"):
        eng.check_invariants()
    eng.slab.advanced[slot] -= 1
    eng.run()
    eng.check_invariants()


def test_state_counters_reach_the_registry_and_the_trace(model):
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    eng = ServingEngine(model, metrics=True, trace=True, **ENGINE)
    rid = eng.add_request(_prompts(9, (20,))[0], 3)
    eng.run()
    scalars = eng.metrics.scalars()
    for key in ("ssm_lane_steps", "ssm_live_lane_steps", "ssm_scan_rows",
                "ssm_scan_row_passes", "state_resets"):
        assert scalars[f"serving_{key}"] == eng.stats[key] > 0, key
    assert scalars["serving_state_slab_bytes"] == eng.slab.hbm_bytes()
    resets = [e for e in eng.tracer.events if e["name"] == "state_reset"]
    assert [e["tid"] for e in resets] == [rid]
    # all absent, not zero, for a model without state
    gpt = GPTForPretraining(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64, dropout=0.0))
    plain = ServingEngine(gpt, metrics=True, max_slots=2, page_size=16)
    assert plain.slab is None
    assert not [k for k in plain.stats if k.startswith(("ssm_", "state_"))]
    assert not [k for k in plain.metrics.scalars()
                if "ssm" in k or "state_" in k]
    assert set(plain.attention_paths()) == {"decode", "prefill"}
