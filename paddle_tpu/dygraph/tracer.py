"""Eager op tracer with tape autograd.

Parity: ``Tracer::TraceOp`` (`/root/reference/paddle/fluid/imperative/tracer.cc:144`)
— runs the kernel, wraps outputs in Tensors, and creates a grad node when any
input requires grad (tracer.cc:231 CreateGradOpNode).  Backward execution
lives in :mod:`engine` (BasicEngine parity).

TPU-first: each (op, attrs) pair is compiled ONCE by XLA via ``jax.jit`` and
re-dispatched by shape — the eager fast path the reference gets from its
generated ``core.ops.*`` C functions, but with kernel fusion inside each op
and no Python→C++ marshalling layer.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ..framework import unique_name
from ..ops import registry

_state = threading.local()


def _records() -> List:
    if not hasattr(_state, "records"):
        _state.records = []
    return _state.records


def has_grad() -> bool:
    return getattr(_state, "grad_enabled", True)


def set_grad_enabled(flag: bool) -> bool:
    old = has_grad()
    _state.grad_enabled = flag
    return old


# AMP state (parity: imperative/amp_auto_cast.* — tracer-level autocast)
def amp_state():
    return getattr(_state, "amp", None)


def set_amp_state(st) -> None:
    _state.amp = st


class GradRecord:
    """One taped forward op (parity: OpBase + GradOpNode, op_base.h:33,202).

    ``snap`` pins the array VALUES of every involved tensor at trace time
    (free: jax arrays are immutable, this stores references) so later
    in-place mutation of a tensor cannot corrupt backward — the version-
    counter guarantee the reference gets from VarBase inplace_version."""

    __slots__ = ("seq", "type", "inputs", "outputs", "attrs", "rng", "snap",
                 "__weakref__")

    _counter = [0]

    def __init__(self, type: str, inputs, outputs, attrs, rng=None):
        GradRecord._counter[0] += 1
        self.seq = GradRecord._counter[0]
        self.type = type
        self.inputs = inputs  # slot -> list[Tensor]
        self.outputs = outputs  # slot -> list[Tensor]
        self.attrs = attrs
        self.rng = rng
        self.snap = {}
        for ts in list(inputs.values()) + list(outputs.values()):
            for t in ts:
                self.snap[id(t)] = t._array

    # Operator-duck-type for registry.make_grad_op_descs
    def input(self, slot):
        return [t.name for t in self.inputs.get(slot, [])]

    def output(self, slot):
        return [t.name for t in self.outputs.get(slot, [])]


# ---------------------------------------------------------------------------
# jit-cached eager kernel execution
# ---------------------------------------------------------------------------

# ops whose output shape depends on input VALUES — cannot jit eagerly
_NONJIT = frozenset({"where_index", "unique", "masked_select", "bincount", "histogram"})

_jit_cache: Dict[Any, Any] = {}

# When True, kernels run inline (no per-op inner-jit wrapper) so the whole
# traced program is ONE flat jaxpr.  Measured: the inner-jit grouping wins
# on transformers (+4.4 MFU GPT, +5.7 BERT) and is neutral on ResNet-50
# (XLA reaches the same conv+BN+ReLU fusion either way) — so False is the
# right default; the toggle exists for per-workload experiments.
_INLINE_KERNELS = False


def set_inline_kernels(flag: bool) -> bool:
    """Toggle per-op inner-jit wrapping; returns the previous value."""
    global _INLINE_KERNELS
    old = _INLINE_KERNELS
    _INLINE_KERNELS = bool(flag)
    return old


def in_manual_mesh_context() -> bool:
    """True inside a shard_map manual region (axis_types carry Manual)."""
    m = jax.sharding.get_abstract_mesh()
    return any("Manual" in str(t) for t in m.axis_types)


def run_eager_kernel(op_type: str, ins: Dict[str, List[Any]], attrs: Dict[str, Any], rng=None):
    """Execute a registered kernel eagerly through a jit cache."""
    op_def = registry.get_op_def(op_type)
    if op_type in _NONJIT:
        return registry.run_kernel(op_def, ins, attrs, rng=rng)
    # Inside a shard_map MANUAL region (pipeline stages, ring attention):
    # run the kernel inline.  jax >= 0.9 avals carry the mesh axis types, so
    # reusing an inner-jit trace across Manual/Auto contexts is unsound.
    # Under plain jit/grad the inner-jit wrapper is KEPT deliberately: the
    # nested pjit boundaries guide XLA's fusion grouping — measured +4.4 MFU
    # points on the GPT bench vs inlining every op into one flat jaxpr.
    if _INLINE_KERNELS or in_manual_mesh_context():
        return registry.run_kernel(op_def, ins, attrs, rng=rng)
    try:
        key = (op_type, registry._freeze(attrs))
        hash(key)
    except TypeError:
        return registry.run_kernel(op_def, ins, attrs, rng=rng)
    fn = _jit_cache.get(key)
    if fn is None:
        frozen_attrs = dict(attrs)

        def _call(kins, rng_):
            return registry.run_kernel(op_def, kins, frozen_attrs, rng=rng_)

        fn = jax.jit(_call)
        _jit_cache[key] = fn
    return fn(ins, rng)


# ---------------------------------------------------------------------------
# trace_op: the dygraph dispatch entry
# ---------------------------------------------------------------------------


def _to_array(v):
    from .tensor import Tensor

    if isinstance(v, Tensor):
        return v._array
    if isinstance(v, (jax.Array, np.ndarray)):
        return v
    return np.asarray(v)


def _prof_active() -> bool:
    """True when paddle_tpu.profiler is collecting op-level host events."""
    import sys

    prof = sys.modules.get("paddle_tpu.profiler")
    return prof is not None and prof.is_profiling()


def trace_op(op_type: str, inputs: Dict[str, Any], attrs: Dict[str, Any]):
    """Run one op eagerly; returns slot -> list[Tensor]."""
    from .tensor import Tensor

    op_def = registry.get_op_def(op_type)

    norm: Dict[str, List[Tensor]] = {}
    for slot, vals in inputs.items():
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        ts = []
        for v in vals:
            if v is None:
                continue
            if not isinstance(v, Tensor):
                v = Tensor(_to_array(v), stop_gradient=True)
            ts.append(v)
        if ts or slot in op_def.list_slots:
            norm[slot] = ts

    amp = amp_state()
    if amp is not None:
        from ..amp.auto_cast import maybe_autocast_inputs

        norm, attrs = maybe_autocast_inputs(amp, op_type, norm, attrs)

    ins_arrays = {slot: [t._array for t in ts] for slot, ts in norm.items()}

    rng = None
    if op_def.needs_rng:
        from ..framework.random import next_rng_key

        rng = next_rng_key()

    from ..framework import flags

    if flags.flag("FLAGS_benchmark") or _prof_active():
        from ..profiler import RecordEvent

        with RecordEvent(op_type):
            outs = run_eager_kernel(op_type, ins_arrays, attrs, rng=rng)
            if flags.flag("FLAGS_benchmark"):
                jax.block_until_ready(outs)
    else:
        outs = run_eager_kernel(op_type, ins_arrays, attrs, rng=rng)

    if flags.flag("FLAGS_check_nan_inf"):
        from ..framework.nan_inf import assert_all_finite_eager

        assert_all_finite_eager(op_type, outs)

    requires_grad = (
        has_grad()
        and not op_def.no_grad
        and any(
            not t.stop_gradient
            for slot, ts in norm.items()
            if slot not in op_def.nondiff_slots
            for t in ts
        )
    )

    out_tensors: Dict[str, List[Tensor]] = {}
    for slot, vals in outs.items():
        stop = (not requires_grad) or (slot in op_def.nondiff_out_slots)
        out_tensors[slot] = [Tensor(v, stop_gradient=stop) for v in vals]

    if requires_grad:
        rec = GradRecord(op_type, norm, out_tensors, dict(attrs), rng=rng)
        for slot, ts in out_tensors.items():
            if slot not in op_def.nondiff_out_slots:
                for t in ts:
                    t.grad_node = rec
        _register_consumers(rec, (t for ts in norm.values() for t in ts))
    return out_tensors


def _register_consumers(rec, tensors):
    """Weakly index which records consume each tensor, so taped in-place
    mutation (Tensor._taped_inplace) can re-point prior consumers at the
    pre-mutation clone (the reference's inplace_version bookkeeping role)."""
    import weakref

    wr = weakref.ref(rec)
    for t in tensors:
        lst = t.__dict__.get("_consumers")
        if lst is None:
            lst = t._consumers = []
        lst.append(wr)
        # compact dead refs at power-of-two sizes — keeps long-lived params'
        # consumer lists O(live records), not O(total ops ever)
        n = len(lst)
        if n >= 64 and (n & (n - 1)) == 0:
            lst[:] = [w for w in lst if w() is not None]


def trace_fn(fn, tensors: List, name: str = "pyfunc"):
    """Trace an arbitrary jax-traceable python function of tensor arrays.

    Used for composite surface ops (indexing, custom PyLayer-like closures).
    Gradients come from ``jax.vjp`` of ``fn`` replayed at backward time —
    the dygraph analogue of the registry's auto-vjp grad ops.

    In STATIC mode the closure is registered as a one-off op and appended to
    the program (auto-vjp grads apply), so composite surface functions work
    in both modes.
    """
    from .tensor import Tensor
    from ..framework import program as fw

    if not fw.in_dygraph_mode():
        return _trace_fn_static(fn, tensors, name)

    arrays = [t._array for t in tensors]
    out_arrays = fn(*arrays)
    single = not isinstance(out_arrays, (list, tuple))
    if single:
        out_arrays = [out_arrays]
    requires_grad = has_grad() and any(not t.stop_gradient for t in tensors)
    outs = [Tensor(a, stop_gradient=not requires_grad) for a in out_arrays]
    if requires_grad:
        rec = PyFuncRecord(fn, tensors, outs, single)
        for t in outs:
            t.grad_node = rec
        _register_consumers(rec, tensors)
    return outs[0] if single else outs


_pyfunc_counter = [0]


def _trace_fn_static(fn, tensors, name):
    """Static-mode trace_fn: register the closure as a one-off op type and
    append it to the current block (grads come from the auto-vjp maker)."""
    from ..ops.dispatch import dispatch_static

    _pyfunc_counter[0] += 1
    op_type = f"__pyfunc_{name}_{_pyfunc_counter[0]}"

    def kernel(kins, attrs):
        xs = kins["X"]
        if not isinstance(xs, list):
            xs = [xs]
        out = fn(*xs)
        if isinstance(out, (list, tuple)):
            return {"Out": list(out)}
        return {"Out": [out]}

    od = registry.register_ephemeral(registry.OpDef(
        type=op_type, kernel=kernel, list_slots={"X", "Out"}
    ))
    outs = dispatch_static(op_type, {"X": list(tensors)}, {})
    # the appended Operator keeps the ephemeral OpDef (and its captured
    # closure) alive exactly as long as the Program that owns it
    from ..framework import program as fw

    fw.default_main_program().current_block().ops[-1]._ephemeral_def = od
    res = outs["Out"]
    return res[0] if len(res) == 1 else res


class PyLayerRecord:
    """Tape node for user-defined PyLayer forward/backward pairs
    (parity: imperative/py_layer_fwd.h + autograd/py_layer.py:1).  Shares the
    PyFuncRecord interface (inputs_list/outputs_list) so collection/release
    logic applies; backward calls the user's staticmethod instead of vjp."""

    __slots__ = ("seq", "cls", "ctx", "inputs_list", "outputs_list",
                 "in_arrays", "__weakref__")

    def __init__(self, cls, ctx, inputs_list, outputs_list):
        GradRecord._counter[0] += 1
        self.seq = GradRecord._counter[0]
        self.cls = cls
        self.ctx = ctx
        self.inputs_list = inputs_list
        self.outputs_list = outputs_list
        self.in_arrays = [t._array for t in inputs_list]


class PyFuncRecord:
    """Tape node for trace_fn closures (PyLayer-style custom autograd).
    ``in_arrays`` snapshots input values at trace time (see GradRecord.snap)."""

    __slots__ = ("seq", "fn", "inputs_list", "outputs_list", "single",
                 "in_arrays", "__weakref__")

    def __init__(self, fn, inputs_list, outputs_list, single):
        GradRecord._counter[0] += 1
        self.seq = GradRecord._counter[0]
        self.fn = fn
        self.inputs_list = inputs_list
        self.outputs_list = outputs_list
        self.single = single
        self.in_arrays = [t._array for t in inputs_list]
