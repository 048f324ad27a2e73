"""Seeded open-loop request schedule: arrivals on a clock, whether or not
earlier requests have finished.

Parameters (the traffic file): ``prompt_len`` / ``output_len``, each
``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or ``{"dist":
"uniform", "min", "max"}``; lengths are whole tokens in [min, max].  The
rate comes from the cell.  Optional ``arrival_seed``: the arrival instants
are then drawn from it and not from ``--seed``, so every seed offers the
same instants (the same bursts and lulls) and draws only which request, of
which lengths and tokens, comes at each: a tail of the gaps between tokens
follows the bursts, and a free draw of them moved it more from seed to
seed than a change to the program would.  Optional ``length_seed``: the
order of the lengths is then drawn from it and not from ``--seed``.  Above
capacity a window serves a third of what was sent, in the order it came, so
the order decides which prompts it serves, and a token of a long prompt
costs more than one of a short prompt: a free order moved a saturated
engine's tokens per second by 2.6 % (one standard deviation) from seed to
seed.  With both keys every seed offers one schedule and draws the tokens
(and the job the weights).

The amount of work is fixed and only its order and timing are drawn,
separately for the lead-in (due before 0) and the window (due from 0 on).
Each part gets ``round(rate * its span)`` requests, due at sorted uniform
draws over the part (a Poisson process conditioned on its count, as bursty
as the unconditioned one), with lengths that are the distribution's
quantiles at ``(i + 0.5) / n`` in a seeded order.  Every seed then offers
the window the same number of requests of the same lengths, so runs differ
by what the system does and not by the draw: a window holds tens of
requests, not the hundreds a free draw would need.

Prompt tokens are uniform over the published vocabulary and every prompt is
distinct.  The whole schedule is drawn before the clock starts; the same
seed gives the same schedule.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    due: float            # seconds on the schedule's clock (0 = window start)
    prompt: np.ndarray    # int32 tokens
    max_new: int


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    """The ``n`` quantiles of ``spec`` at (i + 0.5) / n, as whole lengths in
    a seeded order."""
    u = (rng.permutation(n) + 0.5) / n
    if spec["dist"] == "uniform":
        lo, hi = spec["min"], spec["max"]
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def make(params: dict, *, vocab: int, seed: int, rate: float, start: float,
         end: float, max_total: int) -> list[Request]:
    """Requests due in [start, end) at ``rate`` per second; ``max_total``
    bounds prompt + output (the engine's positions)."""
    rng = np.random.default_rng([seed, 0x09E7])
    at = rng if params.get("arrival_seed") is None else \
        np.random.default_rng([int(params["arrival_seed"]), 0xA771])
    order = rng if params.get("length_seed") is None else \
        np.random.default_rng([int(params["length_seed"]), 0x1E46])
    out = []
    for lo, hi in ((start, min(end, 0.0)), (max(start, 0.0), end)):
        k = round((hi - lo) * rate) if hi > lo else 0
        due = lo + np.sort(at.random(k)) * (hi - lo)
        p_len = _lengths(params["prompt_len"], k, order)
        o_len = _lengths(params["output_len"], k, order)
        for t, p, new in zip(due, p_len, o_len):
            body = rng.integers(0, vocab, int(p)).astype(np.int32)
            out.append(Request(float(t), body[: max_total - int(new)],
                               int(new)))
    return out
