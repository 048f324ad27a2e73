"""Autoregressive GPT decoding with a static-shape KV cache.

Role parity: PaddleNLP ``GPTForGeneration`` / the reference inference
engine's decoder path (``paddle/fluid/inference`` + fused decode kernels).

TPU-first design:
  * the WHOLE generation — prefill + ``max_new_tokens`` decode steps — is
    ONE jitted program: the decode loop is a ``lax.scan`` over a
    pre-allocated ``(L, B, H, S_max, D)`` KV cache updated with
    ``lax.dynamic_update_slice`` (static shapes, no retracing per token);
  * per decode step the query is a single token, so attention is a
    (B, H, 1, S) matvec against the cache — bandwidth-bound, which is why
    the cache lives in bf16 when the params do, and int8 when
    ``GPTConfig.int8`` (or the explicit ``int8=`` knob) asks for it: int8
    values + per-(layer, batch, head, position) fp32 scales halve the
    dominant HBM stream again, with the dequant fused into the attention
    einsum on-chip;
  * with ``int8`` the QKV/output/MLP projections also run W8A8
    (pre-quantized per-output-channel int8 weights + dynamic per-token
    activation quant — ops/quant_ops.w8a8_apply), so decode exercises the
    same numerics the flagship trains through;
  * sampling (greedy / temperature / top-k) runs on-device inside the
    scan with a threaded PRNG key.

Tensor-parallel models work transparently: parameters are global GSPMD
arrays carrying their 'mp' NamedShardings, so the same jitted program
decodes on a tp mesh with XLA inserting the collectives.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .moe import MoESpec, moe_ffn
from .ssm import SSMSpec

_tree_map = jax.tree_util.tree_map


@dataclasses.dataclass(frozen=True)
class MuP:
    """The scalar multipliers of a maximal-update parametrisation, each
    applied where its name says (1 everywhere is no parametrisation):
    ``embedding`` on the token embedding, ``attn_in`` on the normed input
    of the q/k/v projections, ``key`` on k, ``attn_out`` on the attention
    output projection, ``mlp_gate`` on the gated MLP's gate before its
    activation, ``mlp_down`` on the MLP's output, ``head`` on the logits.
    (The state-space mixer's are on its :class:`SSMSpec`.)"""

    embedding: float = 1.0
    attn_in: float = 1.0
    key: float = 1.0
    attn_out: float = 1.0
    mlp_gate: float = 1.0
    mlp_down: float = 1.0
    head: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One decoder layer, as data.  The substrate below (``_block_qkv``,
    ``_block_finish``) and the serving engine's programs read this and the
    block's parameter names; the default is GPT-2's layer.

    ``norm``: ``"layer"`` (LayerNorm; ``norm_bias``: with an offset, or
    gain only) or ``"rms"`` (RMSNorm, gain only, computed in float32).
    ``position``: ``"learned"`` (a table added to the token embedding,
    nothing in the layer), ``"rope"`` (q and k rotated in interleaved pairs
    over all of the head's dims, GPT-J style, by ``rope_theta``),
    ``"rope_half"`` (the same angles, dim ``i`` paired with ``i + d / 2``:
    rotate-half, GPT-NeoX style) or ``"none"``.
    ``window``: causal sliding window of that many keys, or None for full
    causal attention.  ``parallel``: ``x + attn(n) + mlp(n)`` with one norm
    feeding both, instead of GPT-2's two norms in sequence.  ``moe``: the
    expert layer's description, or None for a dense MLP of kind ``mlp``:
    ``"gelu"`` (GPT-2's ``fc1``/``fc2``) or ``"gated_silu"``
    (``(silu(m Wg) * m Wu) Wd``).  ``ssm``: a state-space mixer that reads
    the attention's norm and whose output joins the attention's in the
    residual (``x + attn(n) + ssm(n)``, the MLP after it by ``parallel``),
    or None.  ``mup``: the block's scalar multipliers.
    ``head_dim``: None means ``hidden // n_heads``."""

    norm_bias: bool = True
    position: str = "learned"
    rope_theta: float = 10000.0
    window: Optional[int] = None
    parallel: bool = False
    moe: Optional[MoESpec] = None
    head_dim: Optional[int] = None
    norm: str = "layer"
    mlp: str = "gelu"
    ssm: Optional[SSMSpec] = None
    mup: MuP = MuP()

    def __post_init__(self):
        if self.position not in ("learned", "rope", "rope_half", "none"):
            raise ValueError(f"position kind {self.position!r}")
        if self.norm not in ("layer", "rms"):
            raise ValueError(f"norm kind {self.norm!r}")
        if self.mlp not in ("gelu", "gated_silu"):
            raise ValueError(f"mlp kind {self.mlp!r}")


GPT2_LAYER = LayerSpec()


def decoder_layers(model, attn_window=None):
    """One :class:`LayerSpec` per layer of ``model``: its own
    ``layer_specs()``, or GPT-2's layer under the one window the model (or
    the ``attn_window`` override) sets."""
    if hasattr(model, "layer_specs"):
        if attn_window is not None:
            raise ValueError("attn_window overrides GPT's window only; this "
                             "model states each layer's own")
        return tuple(model.layer_specs())
    window = (attn_window if attn_window is not None
              else getattr(model.cfg, "attn_window", None))
    return (dataclasses.replace(GPT2_LAYER, window=window),) \
        * model.cfg.num_layers


def rope_interleaved(x, pos, theta):
    """Rotate ``x`` (B, heads, T, D) in interleaved pairs (2i, 2i + 1), the
    row at ``pos`` (B, T) by ``pos * theta ** (-2i / D)``; float32 inside."""
    d = x.shape[-1]
    inv = 1.0 / (jnp.float32(theta)
                 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, :, None] * inv      # (B,1,T,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def rope_half(x, pos, theta):
    """Rotate ``x`` (B, heads, T, D) in the pairs (i, i + D / 2)
    (rotate-half), the row at ``pos`` (B, T) by ``pos * theta ** (-2i /
    D)``; float32 inside."""
    d = x.shape[-1]
    inv = 1.0 / (jnp.float32(theta)
                 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, :, None] * inv      # (B,1,T,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (2, d // 2))
    a, b = xf[..., 0, :], xf[..., 1, :]
    # (a concatenate of the two half-width results along the lanes aborts
    # the TPU compiler, libtpu 0.0.34; the stack + reshape does not)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-2).reshape(x.shape).astype(x.dtype)


_ROPE = {"rope": rope_interleaved, "rope_half": rope_half}


def _block_params(blk, int8=False):
    from ..ops.quant_ops import quantize_per_channel

    a, m = blk.attn, blk.mlp
    p = {
        "ln1_g": blk.ln1.weight._array, "ln1_b": blk.ln1.bias._array,
        "qkv_b": a.qkv.bias._array, "proj_b": a.proj.bias._array,
        "ln2_g": blk.ln2.weight._array, "ln2_b": blk.ln2.bias._array,
        "fc1_b": m.fc1.bias._array, "fc2_b": m.fc2.bias._array,
    }
    for name, w in (("qkv", a.qkv.weight), ("proj", a.proj.weight),
                    ("fc1", m.fc1.weight), ("fc2", m.fc2.weight)):
        if int8:
            # one-shot per-output-channel quantization at setup; decode
            # then never touches the fp weights again
            wq, ws = quantize_per_channel(w._array, axis=1)
            p[name + "_wq"], p[name + "_ws"] = wq, ws
        else:
            p[name + "_w"] = w._array
    return p


def _mm(p, name, x):
    """x @ weight — W8A8 int8 when the block params carry quantized
    weights, plain float matmul otherwise."""
    wq = p.get(name + "_wq")
    if wq is not None:
        from ..ops.quant_ops import w8a8_apply

        return w8a8_apply(x, wq, p[name + "_ws"], out_dtype=x.dtype)
    return x @ p[name + "_w"]


def _kv_quant(blk):
    """Symmetric int8 over the head dim: [..., D] -> (int8 [..., D],
    fp32 scale [..., 1]) — one scale per (batch, head, position); the
    quantization decision is the shared per-token rule."""
    from ..ops.quant_ops import quantize_per_token

    return quantize_per_token(blk)


def _kv_quant4(blk):
    """Symmetric int4 over the head dim: [..., D] -> (packed int8
    [..., D//2] nibbles, fp32 scale [..., 1]) — the shared int4 per-token
    rule (ops/quant_ops.quantize_int4_per_token), so the dense cache and
    the paged pool quantize identically."""
    from ..ops.quant_ops import quantize_int4_per_token

    return quantize_int4_per_token(blk)


def _kv_dequant(vals, scale, hd):
    """Dequantize a quantized cache side: int4 nibble caches (packed last
    dim == hd // 2) unpack in the same expression XLA fuses into the
    attention einsum; int8 caches multiply straight through."""
    if vals.shape[-1] != hd:
        from ..ops.quant_ops import unpack_int4

        return unpack_int4(vals).astype(jnp.float32) * scale
    return vals.astype(jnp.float32) * scale


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps) * g
    return y if b is None else y + b


def _times(y, m):
    """``y`` scaled by the multiplier ``m`` in its own type (1: as it is)."""
    return y if m == 1.0 else y * jnp.asarray(m, y.dtype)


def _norm(p, name, x, eps, spec):
    """The block's norm ``name`` (``ln1``, ``ln2``, ``lnf``) of the kind
    ``spec`` states."""
    if spec.norm == "rms":
        xf = x.astype(jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
        return (y * p[name + "_g"].astype(jnp.float32)).astype(x.dtype)
    return _ln(x, p[name + "_g"],
               p[name + "_b"] if spec.norm_bias else None, eps)


def _block_qkv(p, x, n_heads, eps, n_kv_heads=None, spec=GPT2_LAYER,
               pos=None):
    """The block's pre-attention half: LN1 + fused QKV projection + head
    split (+ the rotation of q and k at ``pos`` (B, T) where ``spec`` asks
    for rotary positions).
    Returns ``(q, k_blk, v_blk)``: ``q`` (B, H, T, D) and ``k_blk``/
    ``v_blk`` in the cache's (B, Hkv, T, D) layout.  Under GQA the fused projection is (H + 2*Hkv)*D wide
    and the split is uneven — K/V carry only ``n_kv_heads`` heads.  Shared
    by the dense-cache decoder below and the paged-cache serving engine
    (serving/engine.py) so the two decode substrates cannot fork
    numerically."""
    b, t, h = x.shape
    hd = spec.head_dim or h // n_heads
    nkv = n_heads if n_kv_heads is None else n_kv_heads
    hx = _times(_norm(p, "ln1", x, eps, spec), spec.mup.attn_in)
    qkv = _mm(p, "qkv", hx)
    if "qkv_b" in p:
        qkv = qkv + p["qkv_b"]
    q, k, v = jnp.split(qkv, [n_heads * hd, (n_heads + nkv) * hd], axis=-1)

    def heads(z, n):  # (B, T, n*hd) -> (B, n, T, hd)
        return z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    k = _times(k, spec.mup.key)
    q = heads(q, n_heads)
    k_blk, v_blk = heads(k, nkv), heads(v, nkv)
    rotate = _ROPE.get(spec.position)
    if rotate is not None:
        q = rotate(q, pos, spec.rope_theta)
        k_blk = rotate(k_blk, pos, spec.rope_theta)
    return q, k_blk, v_blk


def _lm_head(p, x, eps, spec=GPT2_LAYER):
    """Final norm (of the kind the model's layers have) + projection to
    fp32 logits over the last axis of ``x``: by the head ``lm_head``
    (V, h) where the model has its own, else by the tied embedding.
    Shared by the dense decoder and the serving engine's
    chunk-prefill/decode programs so the logits math cannot fork."""
    h = _norm(p, "lnf", x, eps, spec)
    logits = (h @ p.get("lm_head", p["wte"]).T).astype(jnp.float32)
    return _times(logits, spec.mup.head)


def _embed(p, toks, pos, spec=GPT2_LAYER):
    """Token embedding (times the model's embedding multiplier), plus the
    learned position table where the model has one (rotary and
    position-free layers take positions themselves)."""
    x = _times(p["wte"][toks], spec.mup.embedding)
    return x + p["wpe"][pos] if "wpe" in p else x


def _block_finish(p, x, out, eps, spec=GPT2_LAYER, valid=None, counts=None,
                  mix=None):
    """The block's post-attention half: output projection residual + MLP
    residual.  ``out`` is the attention output already merged back to the
    activation layout of ``x``; ``mix`` is the state-space mixer's term of
    the same residual, where the block has one.  Shared with
    serving/engine.py.  One body
    for every :class:`LayerSpec`: a bias is added where the block has one,
    the MLP's norm reads the block's input (parallel) or the attention
    residual (sequential), and the MLP is GPT-2's, the gated one or the
    expert layer.  An
    expert layer appends its per-expert row counts to the list ``counts``
    (``valid`` masks padded rows and idle lanes out of them)."""
    def plus(y, name):
        return y + p[name] if name in p else y

    h_in = x
    x = plus(x + _times(_mm(p, "proj", out), spec.mup.attn_out), "proj_b")
    if mix is not None:
        x = x + mix
    hx = _norm(p, "ln1" if spec.parallel else "ln2",
               h_in if spec.parallel else x, eps, spec)
    if spec.moe is None and spec.mlp == "gated_silu":
        mid = jax.nn.silu(_times(_mm(p, "gate", hx), spec.mup.mlp_gate)) \
            * _mm(p, "up", hx)
        return x + _times(_mm(p, "down", mid), spec.mup.mlp_down)
    if spec.moe is None:
        mid = jax.nn.gelu(plus(_mm(p, "fc1", hx), "fc1_b"),
                          approximate=False)
        return plus(x + _mm(p, "fc2", mid), "fc2_b")
    mlp, rows = moe_ffn(p, hx, spec.moe, valid)
    if counts is not None:
        counts.append(rows)
    return x + mlp


def dense_attention(q, k, v, window=None):
    """Causal (optionally sliding-window) attention with no cache: ``q``
    (B, H, T, D), ``k``/``v`` (B, Hkv, T, D) -> (B, T, H * D), float32
    softmax.  The eager forward of a model that the substrate describes."""
    b, h, t, d = q.shape
    nkv = k.shape[1]
    qg = q.reshape(b, nkv, h // nkv, t, d)
    s = jnp.einsum("bngtd,bnsd->bngts", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / np.sqrt(d).astype(np.float32)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    att = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1).astype(v.dtype)
    out = jnp.einsum("bngts,bnsd->bngtd", att, v)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _block_fwd(p, x, k_cache, v_cache, pos, n_heads, eps, n_kv_heads=None,
               window=None):
    """One decoder block over ``x`` (B, T, h) with cache write at ``pos``.

    The KV cache is (B, Hkv, S, D) (Hkv < H under GQA; the attention
    einsums group query heads over the shared K/V head by a reshape, never
    by repeating the cache).  A quantized cache
    arrives as a ``(values, scales)`` tuple per side — int8 values, or
    packed int4 nibbles (last dim D//2, detected from the shape); the new
    K/V block is quantized at the write and the whole cache dequantizes
    INSIDE the attention einsum's producer (XLA fuses the elementwise
    dequant/unpack into the dot), so HBM only ever streams the quantized
    values + one fp32 scale per (b, h, position).  ``window`` applies
    causal sliding-window masking: each query sees only the trailing
    ``window`` positions.

    Works for prefill (T = prompt len, pos = 0) and decode (T = 1,
    pos = current length).  Returns (y, k_cache, v_cache)."""
    b, t, h = x.shape
    hd = h // n_heads
    nkv = n_heads if n_kv_heads is None else n_kv_heads
    q, k_blk, v_blk = _block_qkv(p, x, n_heads, eps, n_kv_heads=n_kv_heads)
    quant_kv = isinstance(k_cache, tuple)
    if quant_kv:
        kq, ksc = k_cache
        vq, vsc = v_cache
        int4_kv = kq.shape[-1] != hd
        quant = _kv_quant4 if int4_kv else _kv_quant
        k_q, k_s = quant(k_blk)
        v_q, v_s = quant(v_blk)
        kq = lax.dynamic_update_slice(kq, k_q, (0, 0, pos, 0))
        ksc = lax.dynamic_update_slice(ksc, k_s, (0, 0, pos, 0))
        vq = lax.dynamic_update_slice(vq, v_q, (0, 0, pos, 0))
        vsc = lax.dynamic_update_slice(vsc, v_s, (0, 0, pos, 0))
        k_cache, v_cache = (kq, ksc), (vq, vsc)
        k_eff = _kv_dequant(kq, ksc, hd)
        v_eff = _kv_dequant(vq, vsc, hd)
    else:
        k_cache = lax.dynamic_update_slice(k_cache, k_blk, (0, 0, pos, 0))
        v_cache = lax.dynamic_update_slice(v_cache, v_blk, (0, 0, pos, 0))
        k_eff, v_eff = k_cache, v_cache
    s_max = k_eff.shape[2]
    grouped = nkv != n_heads
    if grouped:
        g = n_heads // nkv
        qg = q.reshape(b, nkv, g, t, hd)
        scores = jnp.einsum("bngtd,bnsd->bngts", qg, k_eff,
                            preferred_element_type=jnp.float32)
    else:
        scores = jnp.einsum("bhtd,bhsd->bhts", q, k_eff,
                            preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(hd).astype(np.float32)
    # causal + cache-validity mask over global positions
    q_pos = pos + jnp.arange(t)[:, None]
    kv_pos = jnp.arange(s_max)[None, :]
    mask = kv_pos <= q_pos
    if window is not None:
        mask = mask & (kv_pos > q_pos - window)
    bmask = mask[None, None, None] if grouped else mask[None, None]
    scores = jnp.where(bmask, scores, -1e30)
    att = jax.nn.softmax(scores, axis=-1).astype(v_eff.dtype)
    if grouped:
        out = jnp.einsum("bngts,bnsd->bngtd", att, v_eff) \
            .reshape(b, n_heads, t, hd)
    else:
        out = jnp.einsum("bhts,bhsd->bhtd", att, v_eff)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, h).astype(x.dtype)
    return _block_finish(p, x, out, eps), k_cache, v_cache


def _resolve_kv_bits(cfg, int8, kv_bits=None):
    """Effective KV-cache quantization width: an explicit ``kv_bits``
    override wins, then ``cfg.kv_bits``, then the legacy coupling where
    ``int8`` (W8A8 weights) also selects an int8 cache.  Returns
    None / 8 / 4."""
    if kv_bits is None:
        kv_bits = getattr(cfg, "kv_bits", None)
    if kv_bits is None and int8:
        kv_bits = 8
    if kv_bits not in (None, 4, 8):
        raise ValueError(f"kv_bits must be None, 4 or 8, got {kv_bits!r}")
    return kv_bits


def _decoder_setup(model, int8=None, attn_window=None):
    """Shared decode substrate for greedy/sampling and beam search:
    returns ``(params, make_run, int8)`` — the flat param pytree, a
    ``make_run(p)`` producing the cached forward ``run(tokens, pos, kc,
    vc) -> (logits, kc, vc)``, and the RESOLVED int8 flag (single source
    of truth for both the quantized params and the cache dtype).

    ``int8=None`` follows ``cfg.int8``; True quantizes the projection
    weights (W8A8) regardless of how the model trained, so a bf16-trained
    model can be served int8 without a copy.  TP (``use_parallel``)
    models decode through the same program: their weights are global
    GSPMD arrays, so XLA inserts the mp collectives."""
    cfg = model.cfg
    if int8 is None:
        int8 = bool(getattr(cfg, "int8", False))
    gpt = model.gpt
    eps = cfg.layer_norm_eps
    n_heads = cfg.num_heads
    n_kv_heads = getattr(cfg, "num_kv_heads", None) or n_heads
    window = (attn_window if attn_window is not None
              else getattr(cfg, "attn_window", None))
    params = {
        "wte": gpt.embeddings.word_embeddings.weight._array,
        "wpe": gpt.embeddings.position_embeddings.weight._array,
        "lnf_g": gpt.ln_f.weight._array, "lnf_b": gpt.ln_f.bias._array,
        "blocks": [_block_params(b, int8=int8) for b in gpt.blocks],
    }

    def make_run(p):
        def logits_from(x):
            return _lm_head(p, x, eps)

        def run(tokens, pos, kc, vc):
            t = tokens.shape[1]
            pe = p["wpe"][pos + jnp.arange(t)]
            x = p["wte"][tokens] + pe
            new_k, new_v = [], []
            for li, bp in enumerate(p["blocks"]):
                # per-layer cache slice / re-stack via tree ops so the int8
                # (values, scales) tuple caches thread the same code path
                x, k1, v1 = _block_fwd(bp, x, _tree_map(lambda a: a[li], kc),
                                       _tree_map(lambda a: a[li], vc), pos,
                                       n_heads, eps, n_kv_heads=n_kv_heads,
                                       window=window)
                new_k.append(k1)
                new_v.append(v1)
            return (logits_from(x), _tree_map(lambda *xs: jnp.stack(xs), *new_k),
                    _tree_map(lambda *xs: jnp.stack(xs), *new_v))

        return run

    return params, make_run, int8


def _empty_cache(cfg, b, s_max, dtype, int8=False, kv_bits=None):
    hd = cfg.hidden_size // cfg.num_heads
    nkv = getattr(cfg, "num_kv_heads", None) or cfg.num_heads
    kv_bits = _resolve_kv_bits(cfg, int8, kv_bits)
    shape = (cfg.num_layers, b, nkv, s_max, hd)
    if kv_bits is not None:
        vd = hd // 2 if kv_bits == 4 else hd  # int4: two nibbles per byte

        def side():
            return (jnp.zeros(shape[:-1] + (vd,), jnp.int8),
                    jnp.zeros(shape[:-1] + (1,), jnp.float32))

        return side(), side()
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _top_p_mask(logits, top_p):
    """Nucleus filter: keep the SMALLEST prefix of descending-probability
    tokens whose cumulative probability reaches ``top_p``; everything else
    is masked to -1e30.  Pure jnp (sort + cumsum), runs on-device inside
    the decode scan."""
    sl = jnp.sort(logits, axis=-1)[..., ::-1]            # descending
    probs = jax.nn.softmax(sl, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # token i is kept while the mass BEFORE it is < top_p — the boundary
    # token that crosses top_p stays in (standard nucleus semantics), and
    # the top-1 token is always kept
    keep = (cum - probs) < jnp.float32(top_p)
    cutoff = jnp.min(jnp.where(keep, sl, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits < cutoff, -1e30, logits)


def _make_sampler(greedy: bool, temperature: float, top_k: int,
                  top_p: float = 1.0):
    """The on-device token sampler shared by the static-batch decoder and
    the continuous-batching serving engine (serving/engine.py): greedy
    argmax, or temperature -> top-k -> top-p (nucleus) -> categorical."""
    def sample(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1)
        logits = logits.astype(jnp.float32) / jnp.float32(
            max(temperature, 1e-6))
        if top_k > 0:
            kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_p is not None and top_p < 1.0:
            logits = _top_p_mask(logits, top_p)
        return jax.random.categorical(key, logits, axis=-1)

    return sample


def spec_accept_greedy(pred, draft):
    """The greedy rejection rule of speculative decoding (r13), shared by
    the serving engine's verify step and its proof tests so the
    acceptance decision has ONE definition.

    The verify block holds ``[carry, draft[0], .., draft[n-1]]`` at
    positions ``L .. L+n``; ``pred[i]`` is the target model's greedy
    token AFTER consuming block row ``i`` — so draft token ``draft[i]``
    is correct iff ``pred[i] == draft[i]``.  Accept the longest agreeing
    prefix, then emit the target's own token at the first disagreement
    (or the bonus token after a fully-accepted draft).  Every emitted
    token is exactly what sequential greedy decode would have produced,
    which is the whole exactness proof: speculation changes HOW MANY
    positions one dispatch scores, never WHICH token any position gets.

    Returns ``(n_accepted, emitted)`` — ``emitted`` is
    ``draft[:n_accepted] + [pred[n_accepted]]``, between 1 and
    ``len(draft) + 1`` tokens."""
    n = 0
    for d in draft:
        if int(pred[n]) != int(d):
            break
        n += 1
    return n, [int(t) for t in draft[:n]] + [int(pred[n])]


def build_generate_fn(model, max_new_tokens: int, temperature: float = 1.0,
                      top_k: int = 0, greedy: bool = True,
                      top_p: float = 1.0,
                      eos_token_id: Optional[int] = None,
                      int8: Optional[bool] = None,
                      kv_bits: Optional[int] = None,
                      attn_window: Optional[int] = None):
    """Compile ``(ids, seed) -> generated ids`` for a GPTForPretraining.

    Returns ``gen(ids)`` taking a (B, prompt_len) int array and returning
    (B, prompt_len + max_new_tokens) with the continuation appended.
    ``top_p`` < 1.0 enables nucleus sampling (applied after temperature
    and top-k).  With ``eos_token_id`` set, a sequence that emits EOS is
    FINISHED: every later position is masked to EOS (the static-batch
    early-stop — the scan still runs ``max_new_tokens`` steps, shapes are
    static, but finished rows stop changing).  ``int8`` (default:
    ``cfg.int8``) selects W8A8 projections + an int8 KV cache.
    ``kv_bits`` (default ``cfg.kv_bits``; 8 or 4) quantizes only the KV
    cache — 4 packs two nibbles per byte; ``attn_window`` (default
    ``cfg.attn_window``) applies causal sliding-window attention.
    """
    cfg = model.cfg
    params, make_run, int8 = _decoder_setup(model, int8=int8,
                                            attn_window=attn_window)
    sample = _make_sampler(greedy, temperature, top_k, top_p)

    @functools.partial(jax.jit, static_argnums=())
    def gen(p, ids, seed):
        b, t0 = ids.shape
        kc, vc = _empty_cache(cfg, b, t0 + max_new_tokens, p["wte"].dtype,
                              int8=int8, kv_bits=kv_bits)
        run = make_run(p)
        logits, kc, vc = run(ids, 0, kc, vc)
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        tok = sample(logits[:, -1], sub)
        finished = (jnp.zeros((b,), bool) if eos_token_id is None
                    else tok == eos_token_id)

        def step(carry, i):
            # carry token sits at sequence position t0 + i: process it
            # THERE (its K/V fills cache slot t0+i) and sample t0+i+1
            tok, finished, kc, vc, key = carry
            logits, kc, vc = run(tok[:, None], t0 + i, kc, vc)
            key, sub = jax.random.split(key)
            nxt = sample(logits[:, -1], sub)
            if eos_token_id is not None:
                nxt = jnp.where(finished, jnp.asarray(eos_token_id,
                                                      nxt.dtype), nxt)
                finished = finished | (nxt == eos_token_id)
            return (nxt, finished, kc, vc, key), tok

        (last, _, _, _, _), toks = lax.scan(
            step, (tok, finished, kc, vc, key),
            jnp.arange(max_new_tokens - 1))
        out = jnp.concatenate(
            [toks.T, last[:, None]], axis=1) if max_new_tokens > 1 \
            else last[:, None]
        return jnp.concatenate([ids, out.astype(ids.dtype)], axis=1)

    def call(ids, seed: int = 0):
        return gen(params, jnp.asarray(ids), seed)

    return call


def generate(model, ids, max_new_tokens: int = 32, temperature: float = 1.0,
             top_k: int = 0, greedy: bool = True, seed: int = 0,
             top_p: float = 1.0, eos_token_id: Optional[int] = None,
             int8: Optional[bool] = None, kv_bits: Optional[int] = None,
             attn_window: Optional[int] = None):
    """Convenience one-shot API (compiles per (shape, knobs))."""
    from ..dygraph.tensor import Tensor

    arr = ids._array if isinstance(ids, Tensor) else np.asarray(ids)
    fn = build_generate_fn(model, max_new_tokens, temperature, top_k, greedy,
                           top_p=top_p, eos_token_id=eos_token_id, int8=int8,
                           kv_bits=kv_bits, attn_window=attn_window)
    out = fn(arr, seed)
    return Tensor(out, stop_gradient=True) if isinstance(ids, Tensor) else out


def build_beam_search_fn(model, max_new_tokens: int, beam_size: int = 4,
                         length_penalty: float = 0.0,
                         eos_token_id: Optional[int] = None,
                         int8: Optional[bool] = None):
    """Compile beam-search decoding: ``ids (B, T0) -> (B, T0 + new)``.

    Role parity: the reference's ``beam_search``/``beam_search_decode`` op
    pair (``operators/math/beam_search.cu``) and PaddleNLP's
    ``decode_strategy="beam_search"``.  TPU-first shape discipline: beams
    are flattened into the batch dim (B*K rows), every step is ONE
    (B*K)-row forward against the shared KV cache, and the whole search is
    a single ``lax.scan`` — no dynamic shapes, no host round-trips; beam
    reordering is a ``take`` over the cache's row axis.

    Scores are sum of token log-probs; ``length_penalty`` applies the GNMT
    ``((5+len)/6)**alpha`` normalization at final selection.  When
    ``eos_token_id`` is set, finished beams are frozen (only the EOS
    continuation keeps the score; the emitted token stays EOS).
    """
    cfg = model.cfg
    K = beam_size
    params, make_run, int8 = _decoder_setup(model, int8=int8)

    @jax.jit
    def gen(p, ids):
        b, t0 = ids.shape
        V = p["wte"].shape[0]
        run = make_run(p)

        # prefill on the B prompts, then expand to B*K beams (tree ops so
        # int8 (values, scales) caches reorder alongside)
        kc, vc = _empty_cache(cfg, b, t0 + max_new_tokens, p["wte"].dtype,
                              int8=int8)
        logits, kc, vc = run(ids, 0, kc, vc)
        lp = jax.nn.log_softmax(logits[:, -1])            # (B, V)
        scores0, tok0 = lax.top_k(lp, K)                   # (B, K)
        kc = _tree_map(lambda a: jnp.repeat(a, K, axis=1), kc)  # b*K + k
        vc = _tree_map(lambda a: jnp.repeat(a, K, axis=1), vc)
        tokens = tok0.reshape(b * K)
        scores = scores0.reshape(b * K)
        finished = (jnp.zeros((b * K,), bool) if eos_token_id is None
                    else tokens == eos_token_id)
        lengths = jnp.ones((b * K,), jnp.float32)  # generated tokens so far

        def step(carry, i):
            tokens, scores, finished, lengths, kc, vc = carry
            logits, kc2, vc2 = run(tokens[:, None], t0 + i, kc, vc)
            lp = jax.nn.log_softmax(logits[:, -1])         # (B*K, V)
            if eos_token_id is not None:
                # frozen beams: only the EOS continuation survives, at an
                # unchanged score
                frozen = jnp.full((V,), -jnp.inf).at[eos_token_id].set(0.0)
                lp = jnp.where(finished[:, None], frozen[None, :], lp)
            cand = scores[:, None] + lp                    # (B*K, V)
            cand = cand.reshape(b, K * V)
            new_scores, flat = lax.top_k(cand, K)          # (B, K)
            parent = flat // V                             # beam idx in 0..K
            new_tok = flat % V
            rows = (jnp.arange(b)[:, None] * K + parent).reshape(b * K)
            kc2 = _tree_map(lambda a: jnp.take(a, rows, axis=1), kc2)
            vc2 = _tree_map(lambda a: jnp.take(a, rows, axis=1), vc2)
            tokens = new_tok.reshape(b * K)
            scores = new_scores.reshape(b * K)
            finished = jnp.take(finished, rows)
            # beams still live grew by one token; frozen beams keep the
            # length they had when they hit EOS (feeds length_penalty)
            lengths = jnp.take(lengths, rows) + (~finished).astype(
                jnp.float32)
            if eos_token_id is not None:
                finished = finished | (tokens == eos_token_id)
            return ((tokens, scores, finished, lengths, kc2, vc2),
                    (tokens, rows))

        carry = (tokens, scores, finished, lengths, kc, vc)
        (tokens, scores, finished, lengths, _, _), (toks, parents) = lax.scan(
            step, carry, jnp.arange(max_new_tokens - 1))

        # backtrack through the parent pointers to materialize sequences
        def back(carry, sp):
            rows = carry                                  # (B*K,) row ids
            step_toks, step_parents = sp
            tok = jnp.take(step_toks, rows)
            rows = jnp.take(step_parents, rows)
            return rows, tok

        last_rows = jnp.arange(b * K)
        rows, rev = lax.scan(back, last_rows,
                             (toks[::-1], parents[::-1]))
        seq = rev[::-1]                                    # (new-1, B*K)
        first = jnp.take(tok0.reshape(b * K), rows)        # step-0 tokens
        beams = jnp.concatenate([first[None], seq], axis=0)  # (new, B*K)

        # length-penalized selection of the best beam per batch row, using
        # each beam's ACTUAL generated length (frozen at its EOS)
        norm = (jnp.power((5.0 + lengths) / 6.0, length_penalty)
                if length_penalty else jnp.ones_like(lengths))
        best = jnp.argmax((scores / norm).reshape(b, K), axis=1)  # (B,)
        pick = jnp.arange(b) * K + best
        out = jnp.take(beams, pick, axis=1).T              # (B, new)
        return jnp.concatenate([ids, out.astype(ids.dtype)], axis=1)

    def call(ids):
        return gen(params, jnp.asarray(ids))

    return call
