"""jaxpr_audit: the one walker library behind every jaxpr contract.

Four test files grew near-duplicate jaxpr walkers asserting the layout
and dtype contracts (``bsnd`` attention reaches the Pallas kernel with
ZERO transposes, the mq verify kernel at ``q_tile=1`` is jaxpr-identical
to the decode kernel, the flagship train step never promotes to f64).
This module is their single implementation; tests import it instead of
redefining it, and new contracts get their primitive-level assertions
here.

Walk semantics (shared by every helper): equations are visited
recursively through sub-jaxprs carried in ``eqn.params`` (scan/cond/
while bodies, closed-call jaxprs, …), but the walk does NOT descend into
primitives named in ``stop_inside`` — default ``("pallas_call",)``,
because a transpose inside a Pallas kernel body is the kernel's own
VMEM-tile math (``k.T`` on the MXU), not a layout change around the
custom call.  The stopping eqn itself IS visited, so
``count_primitive(jaxpr, "pallas_call")`` counts kernel dispatches.

Helpers accept either a ``ClosedJaxpr`` (what ``jax.make_jaxpr``
returns) or a raw ``Jaxpr``.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Iterable, Iterator, List, Set, Tuple

__all__ = [
    "iter_eqns", "collect_primitives", "count_primitive",
    "count_primitives", "pallas_kernels", "assert_no_primitive",
    "assert_no_transpose",
    "assert_jaxpr_identical", "find_f64", "assert_no_f64",
    "find_dtype_upcasts", "DEFAULT_STOP_INSIDE",
]

DEFAULT_STOP_INSIDE: Tuple[str, ...] = ("pallas_call",)


def _as_jaxpr(jaxpr):
    """Normalize ClosedJaxpr -> Jaxpr (idempotent on raw Jaxprs)."""
    inner = getattr(jaxpr, "jaxpr", None)
    return inner if inner is not None else jaxpr


def _sub_jaxprs(eqn) -> Iterator[object]:
    """Sub-jaxprs an equation carries in its params: ClosedJaxprs (have
    ``.jaxpr``), raw Jaxprs (have ``.eqns``), or lists of either (cond
    branches)."""
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for u in vs:
            inner = getattr(u, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(u, "eqns"):
                yield u


def iter_eqns(jaxpr, stop_inside: Iterable[str] = DEFAULT_STOP_INSIDE
              ) -> Iterator[object]:
    """Yield every equation reachable from ``jaxpr`` (the stop-listed
    primitives' eqns included, their bodies excluded)."""
    stop = tuple(stop_inside)
    for eqn in _as_jaxpr(jaxpr).eqns:
        yield eqn
        if eqn.primitive.name in stop:
            continue
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, stop)


def collect_primitives(jaxpr,
                       stop_inside: Iterable[str] = DEFAULT_STOP_INSIDE
                       ) -> Set[str]:
    """All primitive names reachable outside the stop-listed bodies."""
    return {eqn.primitive.name for eqn in iter_eqns(jaxpr, stop_inside)}


def count_primitive(jaxpr, name: str,
                    stop_inside: Iterable[str] = DEFAULT_STOP_INSIDE
                    ) -> int:
    """Occurrences of one primitive (e.g. ``"transpose"``)."""
    return sum(eqn.primitive.name == name
               for eqn in iter_eqns(jaxpr, stop_inside))


def count_primitives(jaxpr,
                     stop_inside: Iterable[str] = DEFAULT_STOP_INSIDE
                     ) -> Counter:
    """Histogram of primitive names — the profile a layout change
    shifts."""
    return Counter(eqn.primitive.name
                   for eqn in iter_eqns(jaxpr, stop_inside))


def pallas_kernels(jaxpr) -> Counter:
    """Pallas kernel dispatches in the program, counted by
    ``(kernel name, interpreted)`` — the readout of which path a dispatcher
    (``sdpa``, ``w8a8_apply``, ``ring_attention``) traced: a program that
    took a jnp reference holds no entry for that kernel, and one that ran
    through the Pallas interpreter shows ``interpreted=True``.  Descends
    into shard_map/scan/remat bodies like every walker here."""
    return Counter(
        (eqn.params["name"], bool(eqn.params["interpret"]))
        for eqn in iter_eqns(jaxpr) if eqn.primitive.name == "pallas_call")


def assert_no_primitive(jaxpr, name: str, context: str = "",
                        stop_inside: Iterable[str] = DEFAULT_STOP_INSIDE
                        ) -> None:
    n = count_primitive(jaxpr, name, stop_inside)
    assert n == 0, (
        f"{context + ': ' if context else ''}expected zero '{name}' "
        f"primitives, found {n}; full set: "
        f"{sorted(collect_primitives(jaxpr, stop_inside))}")


def assert_no_transpose(jaxpr, context: str = "") -> None:
    """The in-place (``bsnd``) layout contract: q/k/v reach the kernel
    without a single transpose primitive (kernel-internal VMEM-tile
    transposes excluded by the walk)."""
    assert_no_primitive(jaxpr, "transpose", context)


def assert_jaxpr_identical(a, b, context: str = "") -> None:
    """Two jaxprs are the SAME program, asserted on their canonical
    string forms — the guard that keeps a 'defined as' identity (e.g.
    mq verify at q_tile=1 == the decode kernel) from drifting into a
    separately-maintained code path."""
    sa, sb = str(a), str(b)
    if sa == sb:
        return
    # first differing line, for a diagnosable failure
    la, lb = sa.splitlines(), sb.splitlines()
    for i, (x, y) in enumerate(zip(la, lb)):
        if x != y:
            raise AssertionError(
                f"{context + ': ' if context else ''}jaxprs differ at "
                f"line {i}:\n  a: {x.strip()}\n  b: {y.strip()}")
    raise AssertionError(
        f"{context + ': ' if context else ''}jaxprs differ in length: "
        f"{len(la)} vs {len(lb)} lines")


# ---------------------------------------------------------------------------
# dtype discipline
# ---------------------------------------------------------------------------

_F64_RE = re.compile(r"f64\[[^\]]*\]")


def find_f64(jaxpr, include_scalars: bool = False) -> List[str]:
    """Distinct ``f64[...]`` avals appearing anywhere in the jaxpr's
    string form.  Scalars (``f64[]``) are excluded by default:
    ``jax_enable_x64`` stays ON for int64 API parity, and weak-typed
    python-float scalars are harmless — the hazard is ARRAYS silently
    promoting (2x HBM, off the MXU fast path)."""
    text = jaxpr if isinstance(jaxpr, str) else str(jaxpr)
    found = set(_F64_RE.findall(text))
    if not include_scalars:
        found.discard("f64[]")
    return sorted(found)


def assert_no_f64(jaxpr, hint: str = "") -> None:
    bad = find_f64(jaxpr)
    assert not bad, (
        f"float64 arrays leaked into the jaxpr: {bad} — an op is "
        f"promoting under the global x64 flag"
        + (f" ({hint})" if hint else ""))


def find_dtype_upcasts(jaxpr, dst: str = "float64",
                       stop_inside: Iterable[str] = DEFAULT_STOP_INSIDE
                       ) -> List[Tuple[str, List[str], List[str]]]:
    """Equations that INTRODUCE ``dst``: some outvar has the dtype and
    no invar does — the precise op to blame for a promotion, where
    :func:`find_f64` only proves one exists.  Returns
    ``(primitive, in_dtypes, out_dtypes)`` per offending eqn."""
    out: List[Tuple[str, List[str], List[str]]] = []
    for eqn in iter_eqns(jaxpr, stop_inside):
        def dtypes(vs):
            names = []
            for v in vs:
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                names.append(str(dt) if dt is not None else "?")
            return names
        ins, outs = dtypes(eqn.invars), dtypes(eqn.outvars)
        if dst in outs and dst not in ins:
            # scalar-only dst outputs are weak-typed noise, same rule
            # as find_f64
            shaped = [v for v in eqn.outvars
                      if str(getattr(getattr(v, "aval", None), "dtype",
                                     "")) == dst
                      and getattr(getattr(v, "aval", None), "shape", ())]
            if shaped:
                out.append((eqn.primitive.name, ins, outs))
    return out
