"""Required operations and bytes of a ``falcon_h1`` mixer's recurrence, from
shapes and counters alone: the same work whatever implements it.

One row of one layer folds into the state ``(H, P, N)`` with two products
and a sum an element (``exp(dt A) h + dt x (outer) B``) and reads ``y`` off
it with a product and a sum an element (``h C``): ``5 H P N`` operations.
The state is float32 (what the configuration states) and has to be read and
written once by whatever advances it: once a lane in a decode step, once a
chunk in prefill, whatever the number of rows the chunk holds.  A row also
brings its ``x`` (H P), ``B`` and ``C`` (G N each) and takes away its ``y``
(H P), float32 inside the mixer.
"""

from __future__ import annotations

#: the state is float32: the configuration's ``ssm_state_dtype``, which the
#: job's check holds the slab to
STATE_ITEMSIZE = 4


def state_elements(sz: dict) -> int:
    return sz["ssm_heads"] * sz["ssm_head_dim"] * sz["d_state"]


def _row_bytes(sz: dict) -> int:
    return 4 * (2 * sz["ssm_heads"] * sz["ssm_head_dim"]
                + 2 * sz["ssm_groups"] * sz["d_state"])


def state_step_work(sz: dict, *, lane_layers: float) -> tuple[float, float]:
    """(operations, bytes) of ``lane_layers`` decode rows, each one live
    lane in one layer: the whole state in and out, a row's operands."""
    n = state_elements(sz)
    return (5.0 * n * lane_layers,
            lane_layers * (2.0 * STATE_ITEMSIZE * n + _row_bytes(sz)))


def chunk_scan_work(sz: dict, *, row_layers: float,
                    chunk_layers: float) -> tuple[float, float]:
    """(operations, bytes) of ``row_layers`` valid prompt rows (summed over
    layers) that came in ``chunk_layers`` chunk passes: each pass moves the
    state in and out once, each valid row its operands."""
    n = state_elements(sz)
    return (5.0 * n * row_layers,
            chunk_layers * 2.0 * STATE_ITEMSIZE * n
            + row_layers * _row_bytes(sz))
