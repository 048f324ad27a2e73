"""GPT model family — the flagship pretraining workload.

Role parity: PaddleNLP GPT-2/3 (`gpt` modeling built on the reference's
``paddle.nn.TransformerDecoder`` + fleet hybrid parallel; BASELINE.json
config 3: "GPT-3 1.3B/13B with Fleet hybrid sharding + pipeline parallel").

TPU-first:
  * attention = fused ``scaled_dot_product_attention`` (flash/Pallas on TPU);
  * TP via Column/RowParallelLinear + VocabParallelEmbedding when an 'mp'
    mesh axis is active (GSPMD shardings, XLA collectives on ICI);
  * :func:`build_functional_train_step` compiles ONE XLA program for
    fwd+bwd+AdamW over the hybrid mesh — the path bench.py and
    ``__graft_entry__.dryrun_multichip`` exercise.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from .. import nn
from ..nn import functional as F
from .. import tensor_api as T
from ..distributed import mesh as mesh_mod


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden: Optional[int] = None
    max_seq_len: int = 1024
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_parallel: bool = False  # TP layers over the 'mp' axis
    # int8: W8A8 execution for the QKV/output/MLP projections — REAL int8
    # GEMMs (per-output-channel weight quant + dynamic per-token activation
    # quant, int32 MXU accumulation via ops/quant_ops.w8a8_matmul ->
    # kernels/int8_gemm Pallas fusion on TPU) with a straight-through
    # backward, so the same knob serves training (bench.py flagship_int8)
    # and decode (models/generation.py also int8-quantizes the KV cache).
    # Parameters stay float (AdamW masters); quantization is re-derived
    # each step from the live weights and fused by XLA into the update.
    int8: bool = False
    # int8_lm_head additionally quantizes the tied LM head matmul in the
    # eager forward (the functional train step's chunked-CE head stays
    # float: the 50k-vocab logits are numerically the loss-critical path)
    int8_lm_head: bool = False
    # num_kv_heads < num_heads = grouped-query attention (GQA, Ainslie et
    # al.): the QKV projection emits only num_kv_heads K/V heads
    # ((num_heads + 2*num_kv_heads) * head_dim wide instead of 3*hidden)
    # and every attention entry gathers query heads per group INSIDE the
    # kernel — K/V are never repeated to num_heads in HBM, so the decode
    # KV cache and the serving page pool shrink by the group factor.
    # None = num_heads (MHA, the pre-GQA layout, bit-identical).
    num_kv_heads: Optional[int] = None
    # attn_window: sliding-window causal attention (Mistral 7B) — position
    # p attends [p-attn_window+1, p].  Serving recycles KV pages behind
    # the window so long generations stop growing.  None = full causal.
    attn_window: Optional[int] = None
    # kv_bits: decode-time KV cache precision — None stores the model
    # dtype, 8 the per-token int8 layout (also implied by ``int8``), 4
    # packs two nibbles per byte with the same per-position fp32 scales
    # (ops/quant_ops.quantize_int4_per_token), halving KV bytes again.
    # Training numerics are untouched; only generation/serving caches read
    # this knob.
    kv_bits: Optional[int] = None

    def __post_init__(self):
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.num_heads % self.num_kv_heads != 0:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({self.num_kv_heads})")
        if self.attn_window is not None and self.attn_window < 1:
            raise ValueError(f"attn_window must be >= 1, got {self.attn_window}")
        if self.kv_bits not in (None, 4, 8):
            raise ValueError(f"kv_bits must be None, 8 or 4, got {self.kv_bits}")


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=4, num_heads=4,
                     max_seq_len=256, **kw)


def gpt_small(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt_medium(**kw):
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)


def gpt_1p3b(**kw):
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                     max_seq_len=2048, **kw)


def gpt_13b(**kw):
    return GPTConfig(hidden_size=5120, num_layers=40, num_heads=40,
                     max_seq_len=2048, **kw)


def w8a8_linear(x, layer):
    """Run a Linear/ColumnParallel/RowParallel layer's weights through the
    W8A8 int8 matmul (ops/quant_ops.w8a8_matmul: per-output-channel weight
    quant + dynamic per-token activation quant + int8 GEMM, STE backward).

    Works on the layer's PARAMETERS directly, so the int8 and bf16 models
    share layer structure, state_dict keys and RNG consumption — same seed
    gives identical float weights in both modes.  TP weights keep their
    'mp' NamedShardings: the per-output-channel scale of a column-sharded
    [in, out@'mp'] weight is itself 'mp'-sharded, so GSPMD threads the
    scales through tp2 without explicit collectives."""
    from ..ops.dispatch import dispatch

    out = dispatch("w8a8_matmul", {"X": [x], "W": [layer.weight]}, {})
    out = out["Out"][0]
    if getattr(layer, "bias", None) is not None:
        out = T.add(out, layer.bias)
    return out


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.window = cfg.attn_window
        self.dropout = cfg.dropout
        self.int8 = cfg.int8
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        wa = nn.ParamAttr(initializer=init)
        # GQA shrinks the fused projection: [q (H heads) | k | v (Hkv heads
        # each)] — split by GLOBAL widths below, which stays correct under
        # TP because GSPMD arrays are logically global (the column-sharded
        # projection output carries its 'mp' sharding through the split)
        qkv_width = (cfg.num_heads + 2 * cfg.num_kv_heads) * self.head_dim
        if cfg.use_parallel:
            from ..distributed.fleet import meta_parallel as mpp

            self.qkv = mpp.ColumnParallelLinear(
                cfg.hidden_size, qkv_width, weight_attr=wa,
                gather_output=False)
            self.proj = mpp.RowParallelLinear(
                cfg.hidden_size, cfg.hidden_size, weight_attr=wa,
                input_is_parallel=True)
        else:
            self.qkv = nn.Linear(cfg.hidden_size, qkv_width, weight_attr=wa)
            self.proj = nn.Linear(cfg.hidden_size, cfg.hidden_size, weight_attr=wa)

    def _run_qkv(self, x):
        return w8a8_linear(x, self.qkv) if self.int8 else self.qkv(x)

    def _run_proj(self, x):
        return w8a8_linear(x, self.proj) if self.int8 else self.proj(x)

    def forward(self, x):
        hd = self.head_dim
        b, s, h = x.shape
        qkv = self._run_qkv(x)
        w = qkv.shape[-1]
        nkv = self.num_kv_heads * w // (
            (self.num_heads + 2 * self.num_kv_heads) * hd)
        nh = (w - 2 * nkv * hd) // hd
        # bnsd: the flash kernel reads contiguous (bh, s, d) tiles and XLA
        # does the [b,nh,s,hd] transposes around it.  The in-place entry
        # (layout="bsnd", kernels/flash._fwd_call_smajor) saves those
        # transposes but DMAs K/V strided, which costs more than it saves.
        q, k, v = T.split(qkv, [nh * hd, nkv * hd, nkv * hd], axis=-1)
        q = T.transpose(T.reshape(q, [b, s, nh, hd]), [0, 2, 1, 3])
        k = T.transpose(T.reshape(k, [b, s, nkv, hd]), [0, 2, 1, 3])
        v = T.transpose(T.reshape(v, [b, s, nkv, hd]), [0, 2, 1, 3])
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=self.dropout,
            training=self.training, window=self.window)
        out = T.reshape(T.transpose(out, [0, 2, 1, 3]), [b, s, nh * hd])
        return self._run_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.int8 = cfg.int8
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        wa = nn.ParamAttr(initializer=init)
        if cfg.use_parallel:
            from ..distributed.fleet import meta_parallel as mpp

            self.fc1 = mpp.ColumnParallelLinear(
                cfg.hidden_size, cfg.ffn_hidden, weight_attr=wa, gather_output=False)
            self.fc2 = mpp.RowParallelLinear(
                cfg.ffn_hidden, cfg.hidden_size, weight_attr=wa, input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(cfg.hidden_size, cfg.ffn_hidden, weight_attr=wa)
            self.fc2 = nn.Linear(cfg.ffn_hidden, cfg.hidden_size, weight_attr=wa)

    def forward(self, x):
        if self.int8:
            return w8a8_linear(F.gelu(w8a8_linear(x, self.fc1)), self.fc2)
        return self.fc2(F.gelu(self.fc1(x)))


class GPTBlock(nn.Layer):
    """Pre-LN decoder block — homogeneous, so the SPMD pipeline engine can
    stack it over the 'pp' axis."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.mlp = GPTMLP(cfg)

    def forward(self, x):
        x = x + self.attn(self.ln1(x))
        x = x + self.mlp(self.ln2(x))
        return x


class GPTEmbeddings(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        if cfg.use_parallel:
            from ..distributed.fleet import meta_parallel as mpp

            self.word_embeddings = mpp.VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        else:
            self.word_embeddings = nn.Embedding(
                cfg.vocab_size, cfg.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        self.position_embeddings = nn.Embedding(
            cfg.max_seq_len, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init))
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, ids):
        b, s = ids.shape
        pos = T.arange(0, s, 1, dtype="int64")
        pe = self.position_embeddings(pos)
        return self.dropout(self.word_embeddings(ids) + pe)


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.blocks = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, ids):
        x = self.embeddings(ids)
        for blk in self.blocks:
            x = blk(x)
        return self.ln_f(x)


class GPTForPretraining(nn.Layer):
    """LM head tied to the word embedding (PaddleNLP GPTForPretraining parity)."""

    def __init__(self, model_or_cfg):
        super().__init__()
        self.gpt = model_or_cfg if isinstance(model_or_cfg, GPTModel) else GPTModel(model_or_cfg)
        self.cfg = self.gpt.cfg

    def forward(self, ids):
        x = self.gpt(ids)
        w = self.gpt.embeddings.word_embeddings.weight
        if self.cfg.int8 and self.cfg.int8_lm_head:
            from ..ops.dispatch import dispatch

            # tied head through the same W8A8 entry ([V, H] weight,
            # per-vocab-row scales via transpose_y)
            return dispatch("w8a8_matmul", {"X": [x], "W": [w]},
                            {"transpose_y": True})["Out"][0]
        return T.matmul(x, w, transpose_y=True)


class GPTPretrainingCriterion(nn.Layer):
    """Next-token CE (vocab-parallel when logits are mp-sharded)."""

    def forward(self, logits, labels, loss_mask=None):
        loss = F.softmax_with_cross_entropy(logits, T.unsqueeze(labels, [-1]))
        loss = T.squeeze(loss, [-1])
        if loss_mask is not None:
            return T.divide(T.sum(T.multiply(loss, loss_mask)),
                            T.maximum(T.sum(loss_mask), T.full_like(T.sum(loss_mask), 1.0)))
        return T.mean(loss)


# ---------------------------------------------------------------------------
# Pipeline-parallel GPT (PipelineLayer form)
# ---------------------------------------------------------------------------


def _embed_head_fwd(layer, x):
    """Tied LM head: reuse the shared embedding weight (PaddleNLP
    GPTForPretrainingPipe's SharedLayerDesc forward_func pattern)."""
    return T.matmul(x, layer.word_embeddings.weight, transpose_y=True)


def GPTForPretrainingPipe(cfg: GPTConfig, num_stages: Optional[int] = None,
                          **kw):
    """GPT as a ``PipelineLayer`` for the SPMD 1F1B engine.

    Parity: PaddleNLP ``GPTForPretrainingPipe(PipelineLayer)`` — embedding on
    stage 0 via SharedLayerDesc, decoder blocks pipelined, final LN + tied
    head on the last stage.  Here the engine pipelines the homogeneous block
    run over the 'pp' mesh axis and runs embedding/head replicated (engine
    partition: pipeline_engine.PipelineEngine._partition).
    """
    from ..distributed.fleet.meta_parallel import (
        LayerDesc, PipelineLayer, SharedLayerDesc,
    )

    descs = [
        SharedLayerDesc("embed", GPTEmbeddings, None, "weight", cfg),
        *[LayerDesc(GPTBlock, cfg) for _ in range(cfg.num_layers)],
        LayerDesc(nn.LayerNorm, cfg.hidden_size, epsilon=cfg.layer_norm_eps),
        SharedLayerDesc("embed", GPTEmbeddings, _embed_head_fwd, "weight", cfg),
    ]
    return PipelineLayer(
        layers=descs, num_stages=num_stages,
        loss_fn=GPTPretrainingCriterion(), **kw)


# ---------------------------------------------------------------------------
# One-jit functional train step (the bench / multichip path)
# ---------------------------------------------------------------------------


def build_functional_train_step(model: GPTForPretraining, lr: float = 1e-4,
                                beta1=0.9, beta2=0.95, eps=1e-8, wd=0.1,
                                dp_axis="dp", remat=True,
                                ce_chunk_rows: int = 1024,
                                sharding_stage: Optional[int] = None,
                                compute_dtype: Optional[str] = None):
    """Compile fwd+bwd+AdamW into ONE donated XLA program over the hybrid mesh.

    Returns (step_fn, params, opt_state):
      step_fn(params, opt_state, ids, labels) -> (params, opt_state, loss)
    ``params`` is ``(other_leaves, stacked_block_leaves)``: the homogeneous
    decoder blocks are STACKED over the layer dim and the stack's leading dim
    is sharded over the 'pp' mesh axis — each pp group holds only its own
    stage's weights (pipeline memory scaling via GSPMD, the route
    `fleet/meta_parallel/pipeline_parallel.py:114` reaches with send/recv).
    The blocks run under ``lax.scan``, TP params keep their 'mp' specs, and
    ids/labels are expected dp-sharded on the batch dim, so one jit covers
    dp x mp x pp.  ``remat``: True wraps each block in jax.checkpoint
    (reference RecomputeOptimizer role, fluid/optimizer.py:5407); the
    string ``"dots"`` selects selective remat (matmul outputs saved,
    elementwise recomputed); False disables rematerialization.

    ``sharding_stage`` = ZeRO over the 'sharding' mesh axis (parity:
    ``fleet/meta_optimizers/sharding_optimizer.py:503`` and the dygraph
    ``DygraphShardingOptimizer``), GSPMD-style:
      * stage 1 — optimizer state (moments + fp32 masters) stored sharded;
      * stage 2 — additionally, gradients are constrained to the sharded
        layout so XLA reduce-scatters them (instead of all-reduce) and the
        weight update runs in the sharded domain, all-gathering only the
        updated weights;
      * stage 3 — parameters THEMSELVES are stored sharded (FSDP); XLA
        inserts the per-layer all-gathers in forward/backward.
    Default: stage 2 when the 'sharding' axis is >1, else 0.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..dygraph import tracer
    from ..dygraph.tensor import Tensor

    # ``compute_dtype``: store params ONLY in fp32 (they double as the AdamW
    # master weights) and cast to the compute dtype at use inside the step —
    # XLA fuses the converts into the consuming matmuls, so no second full
    # copy of the weights ever lives in HBM.  This replaces the
    # params-bf16 + fp32-master layout (the reference's multi_precision
    # storage) with a TPU-native cast-on-read one, freeing 2 bytes/param.
    cd = None
    if compute_dtype is not None:
        cd = jnp.dtype(compute_dtype)

    def _to_compute(a):
        return a.astype(cd) if (cd is not None and a.dtype != cd
                                and jnp.issubdtype(a.dtype, jnp.floating)) else a

    mesh = mesh_mod.get_mesh()
    pp = mesh_mod.axis_size("pp")
    shd = mesh_mod.axis_size("sharding")
    if sharding_stage is None:
        # honor DistributedStrategy.sharding_configs["stage"] when fleet is up
        try:
            from ..distributed import fleet as fleet_mod

            strat = fleet_mod._fleet_state.get("strategy")
            sharding_stage = int(strat.sharding_configs.get("stage", 2)) if (
                strat is not None and shd > 1) else (2 if shd > 1 else 0)
        except Exception:
            sharding_stage = 2 if shd > 1 else 0
    if shd <= 1:
        sharding_stage = 0

    param_objs = list(model.parameters())
    blocks = list(model.gpt.blocks)
    block_param_objs = [list(b.parameters()) for b in blocks]
    structs = [[(tuple(p.shape), str(p._array.dtype)) for p in ps]
               for ps in block_param_objs]
    # Stack + scan only when a pp axis actually exists: the stacked layout is
    # what gives pipeline memory scaling, but on a single chip the unrolled
    # loop schedules ~1.5x faster (XLA fuses across layer boundaries).
    homogeneous = (pp > 1 and len(blocks) > 1
                   and all(s == structs[0] for s in structs))

    if homogeneous:
        block_ids = {id(p) for ps in block_param_objs for p in ps}
        other_objs = [p for p in param_objs if id(p) not in block_ids]
    else:
        other_objs = param_objs
        block_param_objs = []

    def _layer_spec(arr):
        sh = getattr(arr, "sharding", None)
        if isinstance(sh, NamedSharding):
            spec = list(sh.spec) + [None] * (arr.ndim - len(sh.spec))
            return spec
        return [None] * arr.ndim

    def _add_sharding_axis(spec, shape):
        """Insert the 'sharding' axis on the first free, divisible dim (ZeRO
        partition choice — by-dim instead of the reference's greedy by-size
        param partition, which GSPMD handles better)."""
        out = list(spec)
        used = set()
        for s in out:
            used.update(s if isinstance(s, tuple) else [s])
        if "sharding" in used:
            return out
        for d, (s, n) in enumerate(zip(out, shape)):
            if s is None and n > 0 and n % shd == 0:
                out[d] = "sharding"
                return out
        return out

    def _mesh_put(arr):
        """Ensure every leaf lives on the hybrid mesh (replicated unless a TP
        layer already installed a NamedSharding); ZeRO stage 3 stores params
        sharded (FSDP)."""
        if mesh is None:
            return arr
        sh = getattr(arr, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.devices.size == mesh.devices.size:
            spec = _layer_spec(arr)
        else:
            spec = [None] * arr.ndim
        if sharding_stage >= 3:
            spec = _add_sharding_axis(spec, arr.shape)
        return jax.device_put(arr, NamedSharding(mesh, P(*spec)))

    other = [_mesh_put(p._array) for p in other_objs]
    stacked = []
    if homogeneous:
        for j in range(len(block_param_objs[0])):
            leaves = [ps[j]._array for ps in block_param_objs]
            if mesh is not None:
                # stack on host, then shard straight from host memory — the
                # device never holds the full unsharded (L, ...) stack, so
                # init peak matches the pp-sharded steady state.
                host = np.stack([np.asarray(a) for a in leaves])
                lead = "pp" if pp > 1 else None
                spec = [lead] + _layer_spec(leaves[0])
                if sharding_stage >= 3:
                    spec = spec[:1] + _add_sharding_axis(spec[1:], host.shape[1:])
                st = jax.device_put(host, NamedSharding(mesh, P(*spec)))
            else:
                st = jnp.stack(leaves)
            stacked.append(st)

    def _constrain_dp(x):
        if mesh is not None and mesh_mod.axis_size(dp_axis) > 1:
            return lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(dp_axis)))
        return x

    def fwd(params_tree, ids):
        other_arrays, stacked_leaves = params_tree
        old = [p._array for p in other_objs]
        for p, a in zip(other_objs, other_arrays):
            p._array = _to_compute(a)
        og = tracer.set_grad_enabled(False)
        try:
            x = model.gpt.embeddings(Tensor(ids, stop_gradient=True))._array
            x = _constrain_dp(x)

            def block_fn(blk, objs, leaves, h):
                saved = [p._array for p in objs]
                for p, a in zip(objs, leaves):
                    p._array = _to_compute(a)
                try:
                    return blk(Tensor(h, stop_gradient=True))._array
                finally:
                    for p, a in zip(objs, saved):
                        p._array = a

            def wrap_remat(fn):
                if remat == "dots":
                    # selective remat: keep matmul outputs, recompute the
                    # cheap elementwise/norm ops — a middle ground between
                    # full remat and no-remat
                    return jax.checkpoint(
                        fn, policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable)
                if not isinstance(remat, bool):
                    raise ValueError(
                        f"remat must be True, False, or 'dots'; got {remat!r}")
                return jax.checkpoint(fn) if remat else fn

            if homogeneous:
                tpl_objs = block_param_objs[0]

                def one_block(h, leaves):
                    return _constrain_dp(block_fn(blocks[0], tpl_objs, leaves, h))

                body = wrap_remat(one_block)

                def scan_body(h, leaves):
                    return body(h, leaves), None

                x, _ = lax.scan(scan_body, x, tuple(stacked_leaves))
            else:
                for blk in blocks:
                    x = wrap_remat(lambda h, b=blk: block_fn(b, [], [], h))(x)
            x = model.gpt.ln_f(Tensor(x, stop_gradient=True))._array
            w = model.gpt.embeddings.word_embeddings.weight._array
            return x, w
        finally:
            tracer.set_grad_enabled(og)
            for p, a in zip(other_objs, old):
                p._array = a

    def _chunked_softmax_xent(x2, w, labels1, chunk_rows=1024):
        """CE over a 50k vocab without ever materializing (tokens, vocab)
        logits: the LM-head matmul runs inside a remat'd scan chunk, so peak
        HBM is chunk_rows*vocab*4 instead of tokens*vocab*4 (the round-1
        compile-OOM cause).  Kernel-role parity:
        softmax_with_cross_entropy_op.cu (997 LoC fused CUDA)."""
        n, h = x2.shape
        c = min(chunk_rows, n)
        while n % c:
            c //= 2
        k = n // c

        def body(tot, inp):
            xc, lc = inp
            logits = jnp.dot(xc, w.T, preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, lc[:, None], axis=-1,
                                         mode="clip")[:, 0]
            return tot + jnp.sum(lse - picked), None

        tot, _ = lax.scan(
            jax.checkpoint(body), jnp.zeros((), jnp.float32),
            (x2.reshape(k, c, h), labels1.reshape(k, c)))
        return tot / n

    def loss_fn(params_tree, ids, labels):
        x, w = fwd(params_tree, ids)
        b, s, h = x.shape
        if ce_chunk_rows:
            return _chunked_softmax_xent(x.reshape(b * s, h), w,
                                         labels.reshape(b * s),
                                         chunk_rows=ce_chunk_rows)
        logits = jnp.matmul(x, w.T)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logits.astype(jnp.float32), labels[..., None], axis=-1,
            mode="clip")[..., 0]
        return jnp.mean(lse - picked)

    params_tree = (other, stacked)
    flat_params, treedef = jax.tree_util.tree_flatten(params_tree)

    # per-leaf storage specs + ZeRO grad/opt-state specs
    p_specs = [_layer_spec(p) for p in flat_params]
    if sharding_stage >= 1:
        opt_specs = [_add_sharding_axis(sp, p.shape)
                     for sp, p in zip(p_specs, flat_params)]
    else:
        opt_specs = p_specs

    def _sharding(spec):
        return NamedSharding(mesh, P(*spec)) if mesh is not None else None

    def _zeros_like_f32(p, spec):
        z = jnp.zeros(p.shape, jnp.float32)
        sh = _sharding(spec)
        return jax.device_put(z, sh) if sh is not None else z

    # AdamW state — moments AND master weights in fp32 even when compute
    # params are bf16 (mixed-precision parity: the reference's
    # multi_precision adam keeps FP32 master params; bf16-only updates round
    # sub-ulp deltas to zero and stall training).  Under ZeRO stage >= 1 the
    # state lives sharded over the 'sharding' axis (1/N per device).
    low_precision = any(p.dtype != jnp.float32 for p in flat_params)
    opt_state = {
        "m": [_zeros_like_f32(p, sp) for p, sp in zip(flat_params, opt_specs)],
        "v": [_zeros_like_f32(p, sp) for p, sp in zip(flat_params, opt_specs)],
        # on the mesh like every other leaf: a single-device counter comes
        # back replicated, and the changed input sharding would compile the
        # whole step a second time on the second call
        "t": _mesh_put(jnp.zeros((), jnp.int32)),
    }
    if low_precision:
        masters = [p.astype(jnp.float32) for p in flat_params]
        if sharding_stage >= 1 and mesh is not None:
            masters = [jax.device_put(m, _sharding(sp))
                       for m, sp in zip(masters, opt_specs)]
        opt_state["master"] = masters

    from ..framework import random as _fr

    # drawn from the LIVE seed chain so paddle.seed() controls dropout noise
    # in this path like everywhere else
    _base_key = _fr.next_rng_key()

    def step(params_tree, opt_state, ids, labels):
        # fresh dropout masks per executed step without changing the step
        # signature: fold the traced step counter into a constant base key
        step_key = jax.random.fold_in(_base_key, opt_state["t"])

        def lf(pt, i, l):
            with _fr.trace_rng_scope(step_key):
                return loss_fn(pt, i, l)

        loss, grads = jax.value_and_grad(lf)(params_tree, ids, labels)
        t = opt_state["t"] + 1
        b1t = 1.0 - beta1 ** t.astype(jnp.float32)
        b2t = 1.0 - beta2 ** t.astype(jnp.float32)
        flat_p = jax.tree_util.tree_leaves(params_tree)
        flat_g = jax.tree_util.tree_leaves(grads)
        if sharding_stage >= 2 and mesh is not None:
            # ZeRO-2: land the gradient sum in the sharded layout — XLA emits
            # a reduce-scatter over 'sharding' (x 'dp') instead of all-reduce
            flat_g = [lax.with_sharding_constraint(g, _sharding(sp))
                      for g, sp in zip(flat_g, opt_specs)]
        masters = opt_state.get("master", flat_p)
        new_p, new_m, new_v, new_master = [], [], [], []
        for i, (p, w32, g, m, v) in enumerate(zip(flat_p, masters, flat_g,
                                                  opt_state["m"], opt_state["v"])):
            gf = g.astype(jnp.float32)
            m2 = beta1 * m + (1 - beta1) * gf
            v2 = beta2 * v + (1 - beta2) * jnp.square(gf)
            upd = (m2 / b1t) / (jnp.sqrt(v2 / b2t) + eps) + wd * w32.astype(jnp.float32)
            w_new = w32.astype(jnp.float32) - lr * upd
            new_master.append(w_new)
            pn = w_new.astype(p.dtype)
            if sharding_stage >= 2 and mesh is not None:
                # stage 2: all-gather the updated weights back to the stored
                # layout; stage 3: p_spec itself is sharded (FSDP) — no gather
                pn = lax.with_sharding_constraint(pn, _sharding(p_specs[i]))
            new_p.append(pn)
            new_m.append(m2)
            new_v.append(v2)
        new_state = {"m": new_m, "v": new_v, "t": t}
        if "master" in opt_state:
            new_state["master"] = new_master
        return jax.tree_util.tree_unflatten(treedef, new_p), new_state, loss

    step_jit = jax.jit(step, donate_argnums=(0, 1))
    return step_jit, params_tree, opt_state
