"""Write parity of the engine's ONE pool write, ``_scatter_kv`` (ISSUE 25).

The programs write the paged KV pool in whole pages along its page axis:
gather the pages a block of rows falls in, merge the rows in by a
position mask, scatter the pages back (PERF.md section 3).  That must put
exactly the bytes a plain element-by-element write puts, on every page but
the null page, and leave the null page as it was.  Here the new write runs
against a NumPy loop over rows into a copy of a randomly filled pool, for
fp / int8 / int4 pools and the row patterns that bite.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.ops.quant_ops import (quantize_int4_per_token,
                                      quantize_per_token)
from paddle_tpu.serving import ServingEngine

PS, MAXP, SLOTS, LAYERS, PAGES = 64, 4, 4, 2, 24
MAX_LEN = PS * MAXP          # 256 positions: pages 0..3 of a slot's table


def _tables(rng, n_pages_per_slot):
    """Distinct pool pages per slot, the unallocated tail on the null page."""
    ids = rng.permutation(np.arange(1, PAGES))[:sum(n_pages_per_slot)]
    table = np.zeros((len(n_pages_per_slot), MAXP), np.int32)
    at = 0
    for g, n in enumerate(n_pages_per_slot):
        table[g, :n] = ids[at:at + n]
        at += n
    return table


def _decode(rng):
    """One row per slot; lane 1 is inactive and lane 3 sits at max_seq_len
    (position 256 is past its table: such a lane has nothing left to
    generate, so it is never active)."""
    return (_tables(rng, [1, 1, 2, 4]), np.array([5, 63, 64, MAX_LEN]),
            np.array([[True], [False], [True], [False]]))


def _verify(rng):
    """Blocks of 5 rows: one straddles pages 0/1, one fits a page, one lane
    is off (n_draft -1), one has a short draft ending at a page's last row."""
    n_draft = np.array([4, 2, -1, 1])
    return (_tables(rng, [2, 1, 1, 2]), np.array([62, 10, 60, 126]),
            np.arange(5)[None, :] <= n_draft[:, None])


def _recycled(rng):
    """A sliding-window slot whose dead leading pages were recycled: their
    table entries point at the null page, the write lands further on."""
    table = _tables(rng, [4, 4, 4, 4])
    table[:, :2] = 0
    return table, np.array([130, 191, 192, 255]), np.ones((SLOTS, 1), bool)


def _chunk(start, n_valid, n_pages, c=128):
    def make(rng):
        return (_tables(rng, [n_pages]), np.array([start]),
                (np.arange(c) < n_valid)[None])
    return make


PATTERNS = {
    "decode_inactive_lanes_and_slot_at_max_len": _decode,
    "verify_block_straddles_two_pages": _verify,
    "window_recycled_leading_pages": _recycled,
    "chunk_128_rows_from_mid_page_three_pages": _chunk(40, 128, 3),
    "chunk_last_page_is_tables_last_ends_on_boundary": _chunk(128, 128, 4),
    "chunk_from_mid_page_ends_at_max_len": _chunk(150, 106, 4),
    "chunk_ends_exactly_on_a_page_boundary": _chunk(0, 128, 2),
    "chunk_padded_rows_past_n_valid": _chunk(30, 70, 2),
    "chunk_of_8_rows_inside_one_page": _chunk(17, 5, 1, c=8),
}

@functools.lru_cache(maxsize=None)
def _engine(kv_bits):
    paddle.seed(0)
    model = GPTForPretraining(GPTConfig(
        vocab_size=64, hidden_size=64, num_layers=LAYERS, num_heads=2,
        max_seq_len=MAX_LEN, dropout=0.0))
    model.eval()
    return ServingEngine(model, max_slots=SLOTS, page_size=PS,
                         num_pages=PAGES, kv_bits=kv_bits,
                         use_paged_kernel=False)


def _random_pool(eng, rng):
    """Every byte of every page distinct from what a write would put."""
    out = {}
    for name, buf in eng.pool.buffers.items():
        if buf.dtype == jnp.int8:
            out[name] = rng.randint(-128, 128, buf.shape).astype(np.int8)
        else:
            out[name] = rng.standard_normal(buf.shape).astype(buf.dtype)
    return out


def _element_write(pool, li, table, pos0, valid, rows):
    """The plain reference: row by row, ``pool[li, page, :, offset] = row``
    for every valid row whose position the table covers."""
    want = {name: buf.copy() for name, buf in pool.items()}
    for g, t in zip(*np.nonzero(valid)):
        pos = int(pos0[g]) + int(t)
        if pos >= MAX_LEN:
            continue
        page = table[g, pos // PS]
        for name, new in rows.items():
            want[name][li, page, :, pos % PS, :] = new[g, :, t, :]
    return want


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("kv_bits", [None, 8, 4], ids=["fp", "int8", "int4"])
def test_page_write_equals_element_write(kv_bits, pattern):
    eng = _engine(kv_bits)
    rng = np.random.RandomState(len(pattern) + (kv_bits or 0))
    table, pos0, valid = PATTERNS[pattern](rng)
    pos0 = pos0.astype(np.int32)
    g, t = valid.shape
    heads, d = eng.n_kv_heads, eng.head_dim
    k1 = rng.standard_normal((g, heads, t, d)).astype(np.float32)
    v1 = rng.standard_normal((g, heads, t, d)).astype(np.float32)
    pool = _random_pool(eng, rng)
    li = LAYERS - 1

    @jax.jit
    def write(bufs, table, pos0, valid, k1, v1):
        writes = eng._page_writes(table, pos0, valid)
        return eng._unflat(eng._scatter_kv(eng._flat(bufs), li, writes,
                                           k1, v1))

    got = jax.tree_util.tree_map(
        np.asarray, write(pool, table, pos0, valid, k1, v1))

    rows = {"k": k1, "v": v1}
    if kv_bits is not None:
        # jitted like the program's: XLA turns the scale's division by a
        # constant into a multiplication, an ulp off the eager result
        qf = jax.jit(quantize_int4_per_token if kv_bits == 4
                     else quantize_per_token)
        (rows["k"], rows["ks"]), (rows["v"], rows["vs"]) = (
            map(np.asarray, qf(x)) for x in (k1, v1))
    want = _element_write(pool, li, table, pos0, valid, rows)

    assert set(got) == set(pool)
    for name in pool:
        # the whole pool, bit for bit: the written layer's live pages, the
        # pages no row touched, and every other layer
        np.testing.assert_array_equal(got[name][:, 1:], want[name][:, 1:],
                                      err_msg=name)
        # the reference parks invalid rows nowhere; the program routes them
        # to the null page and must put nothing on it either
        np.testing.assert_array_equal(got[name][:, 0], pool[name][:, 0],
                                      err_msg=f"{name}: null page")
    # no pattern is vacuous: each writes at least one row
    assert all((want[n][li] != pool[n][li]).any() for n in pool)


def test_one_write_holds_each_live_page_once():
    """A page id may occur twice in one scatter only as the null page:
    rows that share a page are merged into one copy of it, and whatever has
    no valid row (inactive lanes, the block's unused last page, positions
    past the table) is routed to page 0 with nothing to put."""
    eng = _engine(None)
    rng = np.random.RandomState(7)
    for make in PATTERNS.values():
        table, pos0, valid = make(rng)
        ids, src, put = map(np.asarray, eng._page_writes(
            jnp.asarray(table), jnp.asarray(pos0, jnp.int32),
            jnp.asarray(valid)))
        live = ids[ids != 0]
        assert len(live) == len(set(live.tolist()))
        assert not put[ids == 0].any()
        assert put.sum() == sum(
            1 for g, t in zip(*np.nonzero(valid)) if pos0[g] + t < MAX_LEN)
        assert src.min() >= 0 and src.max() < valid.shape[1]
