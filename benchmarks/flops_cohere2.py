"""Required operations and bytes of a ``cohere2_moe`` expert layer, from
shapes and routing counts alone: the same work whatever implements it.

One expert on one row is three matrix products, ``(h) x (h, f)`` twice and
``(f) x (f, h)``: ``6 h f`` operations, against ``3 h f`` weights.  A routed
expert is needed for the rows the router sent it; a shared expert for every
row.  An expert's weights have to be read once a pass if it had a row at
all, and not otherwise.
"""

from __future__ import annotations


def expert_params(sz: dict) -> int:
    return 3 * sz["hidden"] * sz["expert_width"]


def expert_layer_work(sz: dict, *, routed_rows: float, row_passes: float,
                      experts_active: float, layer_passes: float,
                      itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the expert layers over an interval.
    ``routed_rows``: (row, held expert) assignments; ``row_passes``: rows
    summed over expert-layer passes; ``experts_active``: held experts that
    had a row, summed over passes; ``layer_passes``: expert-layer passes
    (each reads every shared expert)."""
    rows = routed_rows + row_passes * sz["shared"]
    experts = experts_active + layer_passes * sz["shared"]
    return (2.0 * rows * expert_params(sz),
            float(itemsize) * experts * expert_params(sz))


def expert_op_pattern(sz: dict, rows: tuple[int, ...],
                      float32_output: bool) -> str:
    """A pattern for the device operations of the expert layers, as a
    trace names them (``trace_reduce.short_name``: instruction, result,
    opcode): a fusion with one result of shape ``(experts, rows, width)``
    or ``(experts, width, rows)``, as the compiler lays the stacked
    products out, for the held and for the shared experts, ``rows`` being
    the row counts the engine's programs run (decode slots, chunk
    buckets); and, where the model is served in a narrower type
    (``float32_output``), the float32 ``(rows, hidden)`` fusions in which
    the experts' outputs are weighted and summed: the layer accumulates in
    float32 and the residual stream around it is not float32.

    A v5e trace carries no scopes, so shapes it is; what keeps them honest
    is the compiled program, whose HLO does: ``models/moe.py`` computes
    under ``jax.named_scope("moe_ffn")``, and
    ``benchmarks/tests/test_cell_cohere2.py`` compiles the cell's own programs for a
    described v5e and holds this pattern to that scope (every instruction
    it matches lies in the scope; what it leaves of the scope is the
    router: ``(rows, router width)`` scores, their sort and sums).  On the
    chip (PR 27) it matches ``fusion bf16[16,4096,128]`` and ``fusion
    bf16[4,4096,128]`` (gate and up products of a chunk, held and
    shared), ``convolution_bitcast_fusion bf16[16,32,4096]`` and
    ``bf16[4,32,4096]`` (the same of a decode), ``fusion f32[rows,4096]``
    (down product and weighted sum) and ``convert_reduce_fusion
    f32[rows,4096]`` (the shared experts' mean)."""
    n = "|".join(sorted({str(sz["experts_held"][1]), str(sz["shared"])}))
    t = "|".join(str(r) for r in sorted(set(rows)))
    w = "|".join(sorted({str(sz["expert_width"]), str(sz["hidden"])}))
    shapes = rf"\w+\[({n}),(?:({t}),({w})|({w}),({t}))\]"
    if float32_output:
        shapes += rf"|f32\[({t}),{sz['hidden']}\]"
    return rf"^\S+ (?:{shapes}) fusion$"
