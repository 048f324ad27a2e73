"""SPMD pipeline-parallel engine: 1F1B-style microbatch schedule compiled as
ONE XLA program over the 'pp' mesh axis.

Role parity: ``/root/reference/python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py`` (``PipelineParallel.train_batch``:114, ``_forward``:156,
``_backward``:199), its NCCL p2p transport
(``pp_utils/p2p_communication.py:38-130``), and the optimizer hookup the
reference does through ``HybridParallelOptimizer``.

TPU-first design (SURVEY.md §7 "hard parts"):
  * stage transfer = ``lax.ppermute`` over the 'pp' ICI axis inside
    ``shard_map`` — no send_v2/recv_v2 ops, no comm streams;
  * the whole microbatch loop is a ``lax.scan`` in ONE jitted program, so XLA
    overlaps the ppermute with the next microbatch's compute (the 1F1B
    overlap the reference schedules by hand);
  * backward is ``jax.grad`` THROUGH the scan — no hand-written 1B phase;
  * stage weights live as stacked arrays ``(S, bps, ...)`` sharded over 'pp',
    so each device holds exactly its stage's weights (pp memory scaling);
  * the optimizer (SGD/Momentum/Adam/AdamW, global-norm clip, scheduled LR)
    runs INSIDE the same jitted step — kernels match ``ops/optimizer_ops.py``
    bit-for-bit so pipelined training equals single-device training.

Stage layout: the engine partitions the ``PipelineLayer``'s layer list into
``prologue | homogeneous middle | epilogue``.  The middle (the maximal run of
layers with identical parameter structure, e.g. transformer blocks) is
pipelined over 'pp' with ``blocks_per_stage = len(middle) // S`` layers per
stage.  Prologue (embedding) and epilogue (final LN + tied head + loss)
COMPUTE runs on every pp rank, but their parameters and ALL their optimizer
state are stored sharded 1/S over the 'pp' axis (each param flattened,
padded to a multiple of S, and laid out ``P('pp')``): XLA all-gathers the
bf16/fp32 param at its use site and reduce-scatters the grad back, while
the fp32 master weights and Adam moments never materialize unsharded.  This
is the ZeRO-3-over-pp answer to the reference's stage-resident extra layers
(``pp_layers.py:76`` puts the embedding on stage 0, the head on the last
stage, and needs ``SharedLayerDesc`` + a grad allreduce for the tied
weight): per-rank bytes for the largest tensors in the model scale as 1/S
— better balanced than the reference, which concentrates them on the first
and last ranks — and a tied embedding/head is naturally one shard-stored
parameter whose two use-site grads autodiff sums, no shared-group comm.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ... import mesh as mesh_mod


def spmd_pipeline(stage_fn: Callable, num_stages: int, axis: str = "pp"):
    """Build a pipelined apply: ``(stacked_params, microbatches) -> outputs``.

    stage_fn(params, x) -> y must be jax-traceable with y.shape == x.shape
    (transformer blocks).  ``stacked_params`` is a pytree whose leaves have a
    leading stage dim (S, ...); ``microbatches`` has shape (M, mb, ...).

    The returned function is meant to be called INSIDE shard_map/jit with the
    mesh installed; it handles its own shard_map over the pp axis.
    """

    mesh = mesh_mod.get_mesh()
    S = num_stages

    def per_device(params_block, xs):
        # params_block leaves: (1, ...) — this device's stage params
        stage = lax.axis_index(axis)
        p = jax.tree_util.tree_map(lambda a: a[0], params_block)
        M = xs.shape[0]
        T = M + S - 1
        state = jnp.zeros_like(xs[0])
        outputs = jnp.zeros_like(xs)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t; other stages use the received act
            mb = lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, M - 1), keepdims=False)
            x_in = jnp.where(stage == 0, mb, state)
            y = stage_fn(p, x_in)
            # last stage emits microbatch t-(S-1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            valid = (stage == S - 1) & (t >= S - 1)
            cur = lax.dynamic_index_in_dim(outputs, out_idx, keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, y, cur), out_idx, axis=0
            )
            state = lax.ppermute(y, axis, perm)
            return (state, outputs), None

        (state, outputs), _ = lax.scan(tick, (state, outputs), jnp.arange(T))
        # replicate the last stage's outputs across the pp axis
        outputs = lax.psum(jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)), axis)
        return outputs

    def apply(stacked_params, microbatches):
        param_specs = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
        fn = jax.shard_map(
            per_device, mesh=mesh, in_specs=(param_specs, P()), out_specs=P(),
            check_vma=False,
        )
        return fn(stacked_params, microbatches)

    return apply


# ---------------------------------------------------------------------------
# In-jit optimizer updates — driven through the REGISTERED kernels in
# ops/optimizer_ops.py (jax-traceable), so pipelined training equals
# single-device training by construction, not by a hand-kept copy.
# ---------------------------------------------------------------------------


def _clip_by_global_norm(flat_grads, clip_norm):
    """Functional twin of nn.clip.ClipGradByGlobalNorm (fluid/clip.py):
    scale = clip_norm / max(global_norm, clip_norm)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in flat_grads))
    scale = clip_norm / jnp.maximum(gn, clip_norm)
    return [(g.astype(jnp.float32) * scale).astype(g.dtype) for g in flat_grads]


def _init_opt_state(mode: str, flat_params, hyper):
    def zeros(p):
        z = jnp.zeros(p.shape, jnp.float32)
        sh = getattr(p, "sharding", None)
        if isinstance(sh, NamedSharding):
            z = jax.device_put(z, sh)
        return z

    if mode == "sgd":
        state = {}
    elif mode == "momentum":
        state = {"velocity": [zeros(p) for p in flat_params]}
    elif mode in ("adam", "adamw"):
        # one global beta-pow pair: all params update in lockstep (shape [1]
        # like the reference's beta1_pow_acc accumulator)
        state = {
            "m": [zeros(p) for p in flat_params],
            "v": [zeros(p) for p in flat_params],
            "b1p": jnp.full((1,), hyper["beta1"], jnp.float32),
            "b2p": jnp.full((1,), hyper["beta2"], jnp.float32),
        }
    else:
        raise ValueError(f"unknown optimizer mode {mode!r}")
    if any(p.dtype != jnp.float32 for p in flat_params):
        # fp32 master weights for low-precision params — bf16-only updates
        # round sub-ulp deltas to zero and stall training (multi_precision
        # parity, same rationale as gpt.build_functional_train_step)
        state["master"] = [p.astype(jnp.float32) for p in flat_params]
    return state


def _apply_update(mode: str, hyper, flat_params, flat_grads, opt_state, lr):
    """Returns (new_flat_params, new_opt_state) by invoking the registered
    op kernels (sgd/momentum/adam/adamw from ops/optimizer_ops.py)."""
    from ....ops import optimizer_ops as K

    # NOTE: L2 regularization is folded into the grads BEFORE this function
    # (and before clipping) by the caller — eager Optimizer.step order is
    # _apply_regularization THEN _apply_clip (optimizer/__init__.py:217).
    # adamw per-param decay mask (apply_decay_param_fun): True = decay
    decay_mask = hyper.get("decay_mask") or (True,) * len(flat_params)
    masters = opt_state.get("master")
    work_p = masters if masters is not None else flat_params
    new_p, new_master, new_state = [], [], {}
    if mode == "sgd":
        for p, w, g in zip(flat_params, work_p, flat_grads):
            w_new = K.sgd_kernel(
                {"Param": w, "Grad": g, "LearningRate": lr}, {})["ParamOut"]
            new_master.append(w_new)
            new_p.append(w_new.astype(p.dtype))
    elif mode == "momentum":
        attrs = {"mu": hyper["momentum"],
                 "use_nesterov": hyper.get("use_nesterov", False)}
        vels = []
        for p, w, g, v in zip(flat_params, work_p, flat_grads,
                              opt_state["velocity"]):
            out = K.momentum_kernel(
                {"Param": w.astype(jnp.float32), "Grad": g.astype(jnp.float32),
                 "Velocity": v, "LearningRate": lr}, attrs)
            new_master.append(out["ParamOut"])
            new_p.append(out["ParamOut"].astype(p.dtype))
            vels.append(out["VelocityOut"])
        new_state["velocity"] = vels
    else:  # adam / adamw
        base_attrs = {"beta1": hyper["beta1"], "beta2": hyper["beta2"],
                      "epsilon": hyper["epsilon"]}
        b1p, b2p = opt_state["b1p"], opt_state["b2p"]
        ms, vs = [], []
        out = None
        for i, (p, w, g, m, v) in enumerate(zip(flat_params, work_p, flat_grads,
                                                opt_state["m"], opt_state["v"])):
            gf = g.astype(jnp.float32)
            if mode == "adamw":
                kernel = K.adamw_kernel
                attrs = dict(base_attrs, coeff=hyper.get("coeff", 0.01),
                             with_decay=bool(decay_mask[i]))
            else:
                kernel, attrs = K.adam_kernel, base_attrs
            out = kernel(
                {"Param": w.astype(jnp.float32), "Grad": gf, "Moment1": m,
                 "Moment2": v, "LearningRate": lr,
                 "Beta1Pow": b1p, "Beta2Pow": b2p}, attrs)
            new_master.append(out["ParamOut"])
            new_p.append(out["ParamOut"].astype(p.dtype))
            ms.append(out["Moment1Out"])
            vs.append(out["Moment2Out"])
        new_state = {"m": ms, "v": vs,
                     "b1p": out["Beta1PowOut"] if out is not None else b1p,
                     "b2p": out["Beta2PowOut"] if out is not None else b2p}
    if masters is not None:
        new_state["master"] = new_master
    return new_p, new_state


def extract_opt_config(optimizer) -> Tuple[str, dict, Optional[float]]:
    """Map a paddle_tpu optimizer object to (mode, hyper, clip_norm).

    Raises on configurations the in-jit update cannot honor — a silently
    degraded update (e.g. Lamb treated as SGD) would train a wrong
    trajectory with no warning."""
    from ....nn.clip import ClipGradByGlobalNorm
    from ....regularizer import L2Decay
    from .... import optimizer as opt_mod

    clip = getattr(optimizer, "_grad_clip", None)
    if clip is not None and not isinstance(clip, ClipGradByGlobalNorm):
        raise NotImplementedError(
            f"pipeline engine supports grad_clip=ClipGradByGlobalNorm only, "
            f"got {type(clip).__name__}")
    clip_norm = clip.clip_norm if clip is not None else None

    reg = getattr(optimizer, "regularization", None)
    l2 = 0.0
    if isinstance(reg, L2Decay):
        l2 = reg.coeff
    elif reg is not None:
        raise NotImplementedError(
            f"pipeline engine supports L2Decay regularization only, got {reg}")

    if isinstance(optimizer, opt_mod.AdamW):
        return ("adamw", {"beta1": optimizer._beta1, "beta2": optimizer._beta2,
                          "epsilon": optimizer._epsilon,
                          "coeff": optimizer._coeff, "l2": l2}, clip_norm)
    if isinstance(optimizer, opt_mod.Adam):
        return ("adam", {"beta1": optimizer._beta1, "beta2": optimizer._beta2,
                         "epsilon": optimizer._epsilon, "l2": l2}, clip_norm)
    if isinstance(optimizer, opt_mod.Momentum):
        return ("momentum", {"momentum": optimizer._momentum,
                             "use_nesterov": optimizer._use_nesterov,
                             "l2": l2}, clip_norm)
    if type(optimizer) is opt_mod.SGD:
        return ("sgd", {"l2": l2}, clip_norm)
    raise NotImplementedError(
        f"pipeline engine in-jit update does not support "
        f"{type(optimizer).__name__}; use SGD, Momentum, Adam, or AdamW")


class PipelineEngine:
    """Owns the partitioned params + the compiled pipelined train step.

    Exposed through ``PipelineParallel`` (paddle train_batch API parity).
    """

    def __init__(self, pipeline_layer, loss_fn=None, axis: str = "pp"):
        self.layers = pipeline_layer
        self.axis = axis
        self.mesh = mesh_mod.get_mesh()
        self.S = pipeline_layer.get_num_stages()
        self.loss_fn = loss_fn or pipeline_layer._loss_fn
        self._funcs = list(pipeline_layer._funcs)
        self._partition()
        self._materialize()
        self._step_cache = {}
        self.opt_state = None
        self._opt_key = None
        self._dirty = False
        self._eval_fn = None

    # -- stage partition ---------------------------------------------------
    @staticmethod
    def _sig(entry):
        """Homogeneity signature: layer CLASS tree + scalar config attrs +
        param structure.  Params alone are not enough — two blocks with
        identical weights shapes but different classes (or e.g. different
        window sizes) must not be treated as the same stage_fn."""
        layer, fwd = entry
        from ....nn.layer_base import Layer

        if not isinstance(layer, Layer):
            return None
        ps = list(layer.parameters())
        if not ps:
            return None

        def scalars(l, prefix=""):
            out = [(prefix + "::class", type(l).__name__)]
            for k, v in vars(l).items():
                if k.startswith("_") or k == "training":
                    continue
                if isinstance(v, (int, float, bool, str)):
                    out.append((prefix + k, v))
            for name, sub in getattr(l, "_sub_layers", {}).items():
                out.extend(scalars(sub, prefix + name + "."))
            return out

        # a SharedLayerDesc forward_func changes behavior with the same
        # layer/params — it must split the homogeneous run
        fwd_id = getattr(fwd, "__qualname__", repr(fwd)) if fwd else None
        return (fwd_id, tuple(scalars(layer)),
                tuple((tuple(p.shape), str(p._array.dtype)) for p in ps))

    def _partition(self):
        """Split layers into prologue | homogeneous middle | epilogue."""
        sigs = [self._sig(e) for e in self._funcs]
        best = (0, 0)  # (length, lo)
        i = 0
        while i < len(sigs):
            if sigs[i] is None:
                i += 1
                continue
            j = i
            while j < len(sigs) and sigs[j] == sigs[i]:
                j += 1
            if j - i > best[0]:
                best = (j - i, i)
            i = j
        run_len, lo = best
        usable = (run_len // self.S) * self.S
        if usable < self.S or usable == 0:
            raise ValueError(
                "SPMD pipeline requires a contiguous run of >= num_stages "
                "layers with identical parameter structure (e.g. transformer "
                f"blocks); longest run is {run_len} for {self.S} stages"
            )
        if usable < run_len:
            import warnings

            warnings.warn(
                f"pipeline stage partition: {run_len - usable} of {run_len} "
                f"homogeneous layers do not divide into {self.S} stages and "
                f"will run REPLICATED in the epilogue (duplicated compute, "
                f"no pp memory scaling for them); prefer num_layers a "
                f"multiple of num_stages")
        hi = lo + usable
        self._pro = self._funcs[:lo]
        self._mid = self._funcs[lo:hi]
        self._epi = self._funcs[hi:]
        self.blocks_per_stage = usable // self.S

    def _run_entries(self, entries, t):
        for layer, fwd in entries:
            if fwd is not None:
                t = fwd(layer, t)
            elif isinstance(t, tuple):
                t = layer(*t)
            else:
                t = layer(t)
        return t

    # -- parameter management -------------------------------------------
    def _materialize(self):
        mid_objs = [list(l.parameters()) for l, _ in self._mid]
        mid_ids = {id(p) for ps in mid_objs for p in ps}
        self._mid_objs = mid_objs
        self._tmpl = self._mid[0][0]
        self._tmpl_fwd = self._mid[0][1]  # shared forward_func (or None)
        self._tmpl_objs = mid_objs[0]

        other, seen = [], set()
        from ....nn.layer_base import Layer

        for layer, _ in self._pro + self._epi:
            if not isinstance(layer, Layer):
                continue
            for p in layer.parameters():
                if id(p) in seen or id(p) in mid_ids:
                    continue
                seen.add(id(p))
                other.append(p)
        self._other_objs = other

        mesh = self.mesh
        # prologue/epilogue params: store flattened + padded to a multiple
        # of S and sharded P('pp') — 1/S persistent bytes per rank for the
        # param AND everything _init_opt_state derives from it (master
        # weights, moments inherit this sharding via zeros_like/astype)
        shard = (NamedSharding(mesh, P(self.axis))
                 if mesh is not None else None)
        self._other_meta = []
        self.other = []
        for p in other:
            host = np.asarray(p._array)
            n = host.size
            pad = (-n) % self.S
            self._other_meta.append((tuple(host.shape), host.dtype.name, n))
            flat = np.concatenate([host.reshape(-1),
                                   np.zeros((pad,), host.dtype)])
            self.other.append(
                jax.device_put(flat, shard) if shard is not None
                else jnp.asarray(flat))
        # stack middle params: leaf j -> (S, bps, ...) sharded over pp on dim 0
        bps = self.blocks_per_stage
        self.stacked = []
        for j in range(len(self._tmpl_objs)):
            host = np.stack([np.asarray(ps[j]._array) for ps in mid_objs])
            host = host.reshape((self.S, bps) + host.shape[1:])
            if mesh is not None:
                arr = jax.device_put(host, NamedSharding(mesh, P(self.axis)))
            else:
                arr = jnp.asarray(host)
            self.stacked.append(arr)

    def sync_from_layers(self):
        """Re-materialize the engine's device copies FROM the layer objects —
        required after set_state_dict / checkpoint load, which rewrite the
        Tensors the engine snapshotted at construction.  fp32 master weights
        re-seed from the loaded params (otherwise the next step would resume
        the pre-load trajectory and overwrite the checkpoint); moments are
        kept, matching eager set_state_dict semantics."""
        self._materialize()
        self._dirty = False
        if self.opt_state is not None and "master" in self.opt_state:
            flat_p = jax.tree_util.tree_leaves((self.other, self.stacked))
            self.opt_state["master"] = [p.astype(jnp.float32) for p in flat_p]

    def sync_to_layers(self):
        """Write the engine's (possibly updated) params back into the layer
        objects (for state_dict/save).  No-op when nothing trained since the
        last sync — the host round-trip of every param is not free."""
        if not self._dirty:
            return
        self._dirty = False
        for j, arr in enumerate(self.stacked):
            host = np.asarray(arr)
            flat = host.reshape((self.S * self.blocks_per_stage,) + host.shape[2:])
            for i, ps in enumerate(self._mid_objs):
                ps[j]._array = jnp.asarray(flat[i])
        for p, arr, (shape, _dt, n) in zip(self._other_objs, self.other,
                                           self._other_meta):
            host = np.asarray(arr)
            p._array = jnp.asarray(host[:n].reshape(shape))

    # -- functional applies ----------------------------------------------
    def _apply_block(self, leaves, h):
        """Run the template middle block functionally on array ``h``."""
        from ....dygraph.tensor import Tensor

        saved = [p._array for p in self._tmpl_objs]
        for p, a in zip(self._tmpl_objs, leaves):
            p._array = a
        try:
            tin = Tensor(h, stop_gradient=True)
            t = (self._tmpl_fwd(self._tmpl, tin) if self._tmpl_fwd is not None
                 else self._tmpl(tin))
            return t._array if isinstance(t, Tensor) else t
        finally:
            for p, a in zip(self._tmpl_objs, saved):
                p._array = a

    def _stage_fn(self, leaves_bps, x):
        """One pipeline stage = blocks_per_stage sequential blocks; leaves
        have a leading (bps,) dim."""
        def body(h, leaves):
            return self._apply_block(leaves, h), None

        h, _ = lax.scan(body, x, tuple(leaves_bps))
        return h

    def _swap_other(self, arrays):
        saved = [p._array for p in self._other_objs]
        for p, a in zip(self._other_objs, arrays):
            p._array = a
        return saved

    def _unpack_other(self, packed):
        """Padded-1D shard-stored params -> full-shape arrays for compute.
        Under jit/GSPMD the slice+reshape is where XLA inserts the
        all-gather; the grad of this op is the matching scatter, so grads
        land back on the P('pp') layout elementwise with the opt state."""
        return [a[:n].reshape(shape)
                for a, (shape, _dt, n) in zip(packed, self._other_meta)]

    def _forward_arrays(self, other_arrays, stacked, xs_mb, apply):
        """prologue -> pipelined middle -> epilogue on traced arrays.
        xs_mb: (M, mb, ...); returns the epilogue output Tensor for the
        flattened batch.  ``other_arrays`` are the packed 1/S-sharded
        prologue/epilogue params."""
        from ....dygraph import tracer
        from ....dygraph.tensor import Tensor

        M = xs_mb.shape[0]
        saved = self._swap_other(self._unpack_other(other_arrays))
        og = tracer.set_grad_enabled(False)
        try:
            flat = xs_mb.reshape((-1,) + xs_mb.shape[2:])
            t = self._run_entries(self._pro, Tensor(flat, stop_gradient=True))
            h = t._array if isinstance(t, Tensor) else t
            y = apply(stacked, h.reshape((M, -1) + h.shape[1:]))
            out = y.reshape((-1,) + y.shape[2:])
            return self._run_entries(self._epi, Tensor(out, stop_gradient=True))
        finally:
            tracer.set_grad_enabled(og)
            self._swap_other(saved)

    def _loss_arrays(self, other_arrays, stacked, xs_mb, ys_mb, apply):
        """Full forward + loss on traced arrays.  xs_mb: (M, mb, ...)."""
        from ....dygraph import tracer
        from ....dygraph.tensor import Tensor

        t = self._forward_arrays(other_arrays, stacked, xs_mb, apply)
        og = tracer.set_grad_enabled(False)
        try:
            ys_flat = ys_mb.reshape((-1,) + ys_mb.shape[2:])
            res = self.loss_fn(t, Tensor(ys_flat, stop_gradient=True))
            loss = res._array if isinstance(res, Tensor) else jnp.asarray(res)
            return jnp.mean(loss)
        finally:
            tracer.set_grad_enabled(og)

    # -- compiled train step ----------------------------------------------
    def _get_step(self, mode: str, hyper: dict, clip_norm):
        key = (mode, tuple(sorted(hyper.items())), clip_norm)
        if key in self._step_cache:
            return self._step_cache[key]

        apply = spmd_pipeline(self._stage_fn, self.S, self.axis)

        def step(other, stacked, opt_state, lr, rng_key, xs, ys):
            from ....framework import random as fr

            def total(trainable):
                o, s = trainable
                # fresh per-step randomness for dropout etc.: rng_key is a
                # jit ARGUMENT, so each executed step draws new masks
                with fr.trace_rng_scope(rng_key):
                    return self._loss_arrays(o, s, xs, ys, apply)

            loss, grads = jax.value_and_grad(total)((other, stacked))
            flat_p, treedef = jax.tree_util.tree_flatten((other, stacked))
            flat_g = jax.tree_util.tree_leaves(grads)
            l2 = hyper.get("l2", 0.0)
            if l2:
                # regularization BEFORE clip — eager Optimizer.step order
                flat_g = [g + l2 * p.astype(g.dtype)
                          for p, g in zip(flat_p, flat_g)]
            if clip_norm is not None:
                flat_g = _clip_by_global_norm(flat_g, clip_norm)
            new_p, new_state = _apply_update(
                mode, hyper, flat_p, flat_g, opt_state, lr)
            new_other, new_stacked = jax.tree_util.tree_unflatten(treedef, new_p)
            return new_other, new_stacked, new_state, loss

        jitted = jax.jit(step, donate_argnums=(0, 1, 2))
        self._step_cache[key] = jitted
        return jitted

    def train_step(self, xs_mb, ys_mb, optimizer=None, lr: Optional[float] = None):
        """One pipelined fwd+bwd+update; returns the scalar loss array.

        ``optimizer`` is a paddle_tpu optimizer object (its mode/hyperparams
        are extracted; LR is read per-call so schedulers work) or None (SGD
        with ``lr``).
        """
        if optimizer is not None:
            mode, hyper, clip_norm = extract_opt_config(optimizer)
            lr_val = optimizer.get_lr()
            decay_fn = getattr(optimizer, "_apply_decay_param_fun", None)
            if mode == "adamw" and decay_fn is not None:
                # per-param decay decisions by name; a stacked block leaf is
                # decided by its template param (all blocks share the role)
                names = ([p.name for p in self._other_objs]
                         + [p.name for p in self._tmpl_objs])
                hyper = dict(hyper,
                             decay_mask=tuple(bool(decay_fn(n)) for n in names))
        else:
            mode, hyper, clip_norm = "sgd", {}, None
            lr_val = 1e-3 if lr is None else lr
        okey = (mode, tuple(sorted(hyper.items())))
        if self.opt_state is None or self._opt_key != okey:
            flat_p = jax.tree_util.tree_leaves((self.other, self.stacked))
            self.opt_state = _init_opt_state(mode, flat_p, hyper)
            self._opt_key = okey
        step = self._get_step(mode, hyper, clip_norm)
        from ....framework.random import next_rng_key

        self.other, self.stacked, self.opt_state, loss = step(
            self.other, self.stacked, self.opt_state,
            jnp.asarray(lr_val, jnp.float32), next_rng_key(),
            jnp.asarray(xs_mb), jnp.asarray(ys_mb))
        self._dirty = True
        return loss

    def eval_output(self, xs_mb):
        """Pipelined forward only (no loss): returns the epilogue output for
        the flattened batch.  The jitted forward is cached on the engine and
        TRACED IN EVAL MODE (dropout etc. off) regardless of the layers'
        current training flag — this is the inference path, and the flag is
        only read at trace time."""
        from ....dygraph.tensor import Tensor
        from ....nn.layer_base import Layer

        xs = jnp.asarray(xs_mb)
        if self._eval_fn is None:
            apply = spmd_pipeline(self._stage_fn, self.S, self.axis)
            mods = [l for l, _ in self._funcs if isinstance(l, Layer)]

            @jax.jit
            def fwd(other, stacked, xs):
                # body runs only at trace time: force eval mode for the trace
                was = [m.training for m in mods]
                for m in mods:
                    m.eval()
                try:
                    t = self._forward_arrays(other, stacked, xs, apply)
                    return t._array if isinstance(t, Tensor) else t
                finally:
                    for m, tr in zip(mods, was):
                        (m.train() if tr else m.eval())

            self._eval_fn = fwd
        return self._eval_fn(self.other, self.stacked, xs)
