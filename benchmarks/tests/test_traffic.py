"""The generators reproduce from the seed and differ across seeds."""

import numpy as np

from benchmarks.traffic import open_loop, token_stream

CHAT = {"prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                       "min": 16, "max": 1536},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                       "min": 8, "max": 512}}
DOC = {"prompt_len": {"dist": "uniform", "min": 1024, "max": 1920},
       "output_len": {"dist": "uniform", "min": 16, "max": 64}}


def _sched(seed, params=CHAT, rate=8.0):
    return open_loop.make(params, vocab=50257, seed=seed, rate=rate,
                          start=-5.0, end=30.0, max_total=2048)


def test_open_loop_same_seed_same_schedule():
    a, b = _sched(3), _sched(3)
    assert len(a) == len(b) == 280
    assert all(x.due == y.due and x.max_new == y.max_new
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_open_loop_seeds_differ_and_limits_hold():
    a, b = _sched(3), _sched(4)
    assert [x.due for x in a[:5]] != [y.due for y in b[:5]]
    for r in a:
        assert -5.0 <= r.due < 30.0
        assert 16 <= len(r.prompt) <= 1536 and 8 <= r.max_new <= 512
        assert len(r.prompt) + r.max_new <= 2048
        assert r.prompt.min() >= 0 and r.prompt.max() < 50257
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))
    assert len({r.prompt[:16].tobytes() for r in a}) == len(a)   # unshared
    for r in _sched(5, DOC, rate=1.0):
        assert 1024 <= len(r.prompt) <= 1920 and 16 <= r.max_new <= 64


def test_token_stream_reproduces_and_labels_are_next_tokens():
    p = {"seq": 64, "zipf_a": 1.1, "check_rows": 2}
    a = token_stream.make(p, vocab=1000, seed=7)
    b = token_stream.make(p, vocab=1000, seed=7)
    c = token_stream.make(p, vocab=1000, seed=8)
    ids, labels = a.batch(3, 4)
    assert ids.shape == labels.shape == (4, 64) and ids.dtype == np.int32
    assert np.array_equal(ids, b.batch(3, 4)[0])
    assert not np.array_equal(ids, a.batch(4, 4)[0])
    assert not np.array_equal(ids, c.batch(3, 4)[0])
    assert np.array_equal(ids[:, 1:], labels[:, :-1])
    assert ids.max() < 1000
    # Zipf: the commonest token is far commoner than the median one
    counts = np.bincount(a.batch(0, 256)[0].ravel(), minlength=1000)
    assert counts.max() > 20 * np.median(counts[counts > 0])
    full, _, ref, _ = a.check_batch(8)
    assert full.shape == (8, 64) and np.array_equal(full[:2], ref)
    assert np.array_equal(full[2:4], ref)


def test_schedule_fixes_the_work_and_draws_the_order():
    a, b = _sched(3, rate=1.0), _sched(4, rate=1.0)
    # the lead-in [-5, 0) and the window [0, 30) each get round(rate * span)
    for s in (a, b):
        assert sum(r.due < 0 for r in s) == 5
        assert sum(r.due >= 0 for r in s) == 30
    win = lambda s: [r for r in s if r.due >= 0]        # noqa: E731
    lens = lambda s: sorted(len(r.prompt) for r in s)   # noqa: E731
    outs = lambda s: sorted(r.max_new for r in s)       # noqa: E731
    assert lens(win(a)) == lens(win(b)) and outs(win(a)) == outs(win(b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.due for r in a] != [r.due for r in b]
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))
    # the quantiles straddle the median of the distribution
    assert 200 < np.median(lens(win(a))) < 320
    assert 100 < np.median(outs(win(a))) < 160
    assert all(x.due == y.due for x, y in zip(a, _sched(3, rate=1.0)))


def test_arrival_seed_fixes_the_instants_and_leaves_the_order_to_the_seed():
    params = dict(CHAT, arrival_seed=0)
    a, b = _sched(3, params, rate=1.0), _sched(4, params, rate=1.0)
    assert [r.due for r in a] == [r.due for r in b]
    assert [r.due for r in a] != [r.due for r in _sched(3, rate=1.0)]
    assert [r.due for r in a] != [
        r.due for r in _sched(3, dict(CHAT, arrival_seed=1), rate=1.0)]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert sorted(len(r.prompt) for r in a if r.due >= 0) == sorted(
        len(r.prompt) for r in b if r.due >= 0)
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    assert sum(r.due < 0 for r in a) == 5 and sum(r.due >= 0 for r in a) == 30
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))


def test_length_seed_fixes_the_order_of_the_lengths_and_nothing_else():
    params = dict(CHAT, length_seed=0)
    a, b = _sched(3, params, rate=1.0), _sched(4, params, rate=1.0)
    shape = lambda s: [(len(r.prompt), r.max_new) for r in s]   # noqa: E731
    assert shape(a) == shape(b)
    assert shape(a) != shape(_sched(3, dict(CHAT, length_seed=1), rate=1.0))
    free = _sched(3, rate=1.0)       # the same lengths, paired by the seed
    for k in (0, 1):
        assert sorted(x[k] for x in shape(a)) == sorted(
            x[k] for x in shape(free))
    assert [r.due for r in a] != [r.due for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    # with the instants fixed too, every seed offers one schedule
    both = dict(params, arrival_seed=0)
    c, d = _sched(3, both, rate=1.0), _sched(4, both, rate=1.0)
    assert [(r.due, len(r.prompt), r.max_new) for r in c] == [
        (r.due, len(r.prompt), r.max_new) for r in d]
    assert [r.due for r in c] == [
        r.due for r in _sched(3, dict(CHAT, arrival_seed=0), rate=1.0)]
    assert not np.array_equal(c[0].prompt[:8], d[0].prompt[:8])
    # a mix without the key draws as it did before the key existed
    assert shape(_sched(3)) == shape(_sched(3, dict(CHAT, length_seed=None)))
