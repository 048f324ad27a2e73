"""Cross-lower every Pallas entry for platform ``tpu`` from the CPU.

``interpret=True`` (what every other kernel test runs) never meets the
Mosaic lowering, so a kernel can be exact on the CPU and still not exist
on the chip: the single-query decode kernel was in that state (ISSUE 21).
Lowering with ``interpret=False`` for
``lowering_platforms=("tpu",)`` needs no chip and takes seconds.  It is
LOWERING only — Mosaic's layout and VMEM checks run inside libtpu at
compile time, which ``python chip_smoke.py`` exercises on the chip with
the same cases.

The serving engine's programs are also COMPILED here, ahead of time, by
the libtpu this sandbox has, for a described ``v5e:2x2`` (no chip): the
optimized HLO and ``memory_analysis()`` show whether a program re-lays
out, copies or slices the paged KV pool (ISSUE 25).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from paddle_tpu.utils import compile_cache

@pytest.mark.parametrize("name", list(chip_smoke.KERNEL_CASES))
def test_kernel_lowers_for_tpu(name):
    case = chip_smoke.kernel_case(name)
    lowered = jax.jit(case.kernel).trace(*case.args).lower(
        lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def test_compile_cache_env_wins(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself, code sets no path."""
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.configure() == "/somewhere/else"
    assert calls == []


def test_compile_cache_default_is_fixed_in_tree(monkeypatch):
    """Unset: one fixed git-ignored directory at the checkout's root."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.configure() == want == compile_cache.DEFAULT_DIR
    assert calls == [("jax_compilation_cache_dir", want)]
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------------
# the serving programs keep the KV pool in place (ISSUE 25)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip.  ``get_topology_desc`` loads libtpu, which
    one process at a time may hold: only ever from inside this fixture."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")       # or libtpu logs under /tmp
    env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: keep them out
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    env.undo()


# pools of 4 layers x 128 MiB a side: more than the chip's 128 MiB of VMEM,
# or the compiler parks the whole toy pool there and its prefetches read as
# pool copies.  int4 packs two nibbles a byte, so the kernels take it from
# head size 256 on (``paged_attention.supported``).
_POOLS = {"bf16": dict(kv_bits=None, heads=4, pages=513),
          "int8": dict(kv_bits=8, heads=4, pages=1025),
          "int4": dict(kv_bits=4, heads=2, pages=2049)}
_SLOTS, _PAGE, _SEQ, _SPEC_K, _LAYERS = 8, 64, 512, 4, 4
_RESULT = re.compile(r"^\s*(?:ROOT )?\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
_MOVES = ("copy", "copy-done", "slice", "slice-done", "dynamic-slice")
_BYTES = {"bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1}   # others: 4


def _serving_program(monkeypatch, one_chip, pool, program):
    """(the engine, the program compiled for one v5e chip)."""
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels import paged_prefill as pp
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.serving import ServingEngine

    # the engine asks the running backend whether the Pallas kernels exist
    # and whether to interpret them; here the answer is the chip's
    monkeypatch.setattr(pa, "_backend_is_tpu", lambda: True)
    monkeypatch.setattr(pp, "_backend_is_tpu", lambda: True)
    kind = _POOLS[pool]
    model = chip_smoke._bf16_model(GPTConfig(
        vocab_size=1024, hidden_size=512, num_layers=_LAYERS,
        num_heads=kind["heads"], max_seq_len=_SEQ, dropout=0.0))
    model.eval()
    eng = ServingEngine(model, max_slots=_SLOTS, page_size=_PAGE,
                        num_pages=kind["pages"], spec_k=_SPEC_K,
                        int8=False, kv_bits=kind["kv_bits"])
    assert set(eng.attention_paths().values()) == {"kernel"}

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params, bufs, key = jax.tree_util.tree_map(
        on_chip, (eng.params, eng.pool.buffers, eng._key))
    s, mp = _SLOTS, eng.max_pages
    fn, args = {
        "decode": (eng._decode_fn, (ints(s), ints(s), ints(s, mp), ints(s))),
        "verify": (eng._verify_fn, (ints(s), ints(s, _SPEC_K), ints(s),
                                    ints(s), ints(s, mp))),
        "prefill": (eng._prefill_fn, (ints(eng.chunk_tokens), ints(), ints(),
                                      ints(mp), ints())),
    }[program]
    return eng, fn.lower(params, bufs, *args, key).compile()


@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
@pytest.mark.parametrize("pool", _POOLS)
def test_serving_program_keeps_the_pool_in_place(monkeypatch, one_chip,
                                                 pool, program):
    """No program re-lays out, copies or slices the pool per dispatch: the
    donated buffers are updated in place by whole-page scatters and the
    attention kernels read them through the block table.  The element
    scatter and the ``[li]`` slices this replaced compiled to 4 copies of
    the whole pool and a slice plus a re-layout of every layer, and to
    temporaries the size of the K pool (PERF.md, PR 25)."""
    eng, compiled = _serving_program(monkeypatch, one_chip, pool, program)
    sizes = {name: int(np.prod(b.shape[1:])) * b.dtype.itemsize
             for name, b in eng.pool.buffers.items()}
    layer = sizes["k"]
    if eng.kv_bits is None:
        largest_move, temp_bound = layer - 1, layer
    else:
        # Quantized pools: the kernels take their scales as (pages, Hkv,
        # page_size, 1) fp32, which TPU tiling pads 128 x, so the engine
        # hands them one layer's rows at a time (``_attend_with``): a
        # slice of that layer of K, V and both scale planes remains, and
        # nothing larger.  Tighten to the bf16 rule once the kernels take
        # lane-dense scales (ROADMAP S2).
        largest_move = layer
        temp_bound = 3 * layer + 2 * 128 * sizes["ks"]
    moved = []
    for line in compiled.as_text().splitlines():
        m = _RESULT.match(line)
        if m and m.group(3) in _MOVES:
            dtype, dims, op = m.groups()
            n = _BYTES.get(dtype, 4) * int(np.prod(
                [int(d) for d in dims.split(",") if d] or [1]))
            if n > largest_move:
                moved.append(f"{op} {dtype}[{dims}]")
    assert not moved, f"pool-sized copies or slices: {sorted(set(moved))}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_bound, (temp, temp_bound, layer)


# ---------------------------------------------------------------------------
# two page groups and an expert layer (ISSUE 27)
# ---------------------------------------------------------------------------

def _two_group_engine(monkeypatch):
    """An engine over a ``cohere2_moe`` model described by shapes alone
    (no weights are made): 4 layers (sliding x 3, full), 32 query heads
    over 4 KV heads of 128, 8 of 16 experts held.  Both page groups and
    each expert stack are larger than the chip's 128 MiB of VMEM, so a
    copy of one would not be a prefetch."""
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels import paged_prefill as pp
    from paddle_tpu.models import Cohere2MoeConfig, Cohere2MoeForCausalLM
    from paddle_tpu.serving import ServingEngine

    monkeypatch.setattr(pa, "_backend_is_tpu", lambda: True)
    monkeypatch.setattr(pp, "_backend_is_tpu", lambda: True)
    cfg = Cohere2MoeConfig(
        vocab_size=1024, hidden_size=2048, num_layers=4, num_heads=32,
        num_kv_heads=4, head_dim=128, intermediate_size=4096,
        num_experts=16, num_experts_per_tok=2, num_shared_experts=1,
        experts_held=(8, 8), sliding_window=2048, max_seq_len=4096,
        dtype="bfloat16")

    class ShapesOnly:
        layer_specs = Cohere2MoeForCausalLM.layer_specs

        def __init__(self):
            self.cfg = cfg

        def decoder_params(self):
            def leaf(shape):
                return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

            return {"wte": leaf((cfg.vocab_size, cfg.hidden_size)),
                    "lnf_g": leaf((cfg.hidden_size,)),
                    "blocks": [{n: leaf(s)
                                for n, (s, _) in cfg.leaf_shapes().items()}
                               for _ in range(cfg.num_layers)]}

    eng = ServingEngine(ShapesOnly(), max_slots=64, page_size=_PAGE,
                        max_seq_len=4096, num_pages=2305)
    assert eng.ring is not None
    assert set(eng.attention_paths().values()) == {"kernel"}
    return eng


_TWO_GROUP = {}


def _two_group_program(monkeypatch, one_chip, program):
    """(the engine, the program compiled for one v5e chip), compiled once."""
    if program not in _TWO_GROUP:
        eng = _two_group_engine(monkeypatch)

        def on_chip(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        params, bufs, key = jax.tree_util.tree_map(
            on_chip, (eng.params, eng._device_pool(), eng._key))
        s, mp = eng.max_slots, eng.max_pages
        fn, args = {
            "decode": (eng._decode_fn, (ints(s), ints(s),
                                        (ints(s, mp), ints(s, mp)), ints(s))),
            "prefill": (eng._prefill_fn, (ints(eng.chunk_tokens), ints(),
                                          ints(), (ints(mp), ints(mp)),
                                          ints())),
        }[program]
        _TWO_GROUP[program] = (
            eng, fn.lower(params, bufs, *args, key).compile())
    return _TWO_GROUP[program]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_two_group_program_keeps_pools_and_experts_in_place(
        monkeypatch, one_chip, program):
    """Both page groups stay under PR 25's invariant (no copy or slice the
    size of a layer of either group), the expert weights are read where
    they lie (no copy, slice or transpose of an expert stack), and the
    temporaries stay under one layer of the smaller group."""
    eng, compiled = _two_group_program(monkeypatch, one_chip, program)
    layer = min(int(np.prod(g["k"].shape[1:])) * 2
                for g in (eng.pool.buffers, eng.ring.buffers))
    stack = int(np.prod(eng.params["blocks"][0]["gate_w"].shape)) * 2
    assert layer > 128 << 20 and stack >= 128 << 20
    moved = []
    for line in compiled.as_text().splitlines():
        m = _RESULT.match(line)
        if m and m.group(3) in _MOVES + ("transpose",):
            dtype, dims, op = m.groups()
            n = _BYTES.get(dtype, 4) * int(np.prod(
                [int(d) for d in dims.split(",") if d] or [1]))
            if n >= min(layer, stack):
                moved.append(f"{op} {dtype}[{dims}]")
    assert not moved, f"group- or expert-sized moves: {sorted(set(moved))}"
    assert compiled.memory_analysis().temp_size_in_bytes < layer


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_expert_operations_carry_their_scope(monkeypatch, one_chip, program):
    """``moe_ffn`` computes under ``jax.named_scope("moe_ffn")``, which is
    how a compiled program's expert operations are told from the rest (the
    benchmark holds its trace pattern to it): every instruction of the
    entry computation that reads an expert stack lies in that scope, and
    the scope holds the stacked products, ``(experts, rows, width)`` either
    way round, of the held and of the shared experts of every layer."""
    eng, compiled = _two_group_program(monkeypatch, one_chip, program)
    text = compiled.as_text()
    entry = re.search(r"^ENTRY .*?^\}", text, re.S | re.M).group(0)
    blk = eng.params["blocks"][0]
    stacks = {"bf16[" + ",".join(map(str, blk[n].shape)) + "]"
              for n in ("gate_w", "down_w", "sh_gate_w", "sh_down_w")}
    rows = eng.max_slots if program == "decode" else eng.chunk_tokens
    held, shared = blk["gate_w"].shape[0], blk["sh_gate_w"].shape[0]
    products = {n: 0 for n in (held, shared)}
    for line in entry.splitlines():
        m = _RESULT.match(line)
        if not m or m.group(3) in ("parameter", "get-tuple-element",
                                   "bitcast", "tuple"):
            continue
        scoped = re.search(r'op_name="[^"]*moe_ffn', line) is not None
        operands = line.split("(", 1)[1]
        if any(st in operands for st in stacks):
            assert scoped, f"reads an expert stack outside moe_ffn: {line}"
        dims = [int(d) for d in m.group(2).split(",") if d]
        if scoped and len(dims) == 3 and dims[0] in products \
                and rows in dims[1:]:
            products[dims[0]] += 1
    assert all(n >= _LAYERS for n in products.values()), products


# ---------------------------------------------------------------------------
# recurrent state beside the pages (ISSUE 37)
# ---------------------------------------------------------------------------

def _state_engine(monkeypatch):
    """An engine over Falcon-H1 as the benchmark's cell serves it (its
    configuration file: published widths, 5 layers, 96 slots), described by
    shapes alone: 20 query heads over 4 KV heads (a GQA group of 5), a
    state slab of 2 GB."""
    import json

    from benchmarks import weights_falcon_h1
    from benchmarks.jobs import serve_falcon_h1
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels import paged_prefill as pp
    from paddle_tpu.kernels import ssd
    from paddle_tpu.models import FalconH1ForCausalLM
    from paddle_tpu.serving import ServingEngine

    for mod in (pa, pp, ssd):
        monkeypatch.setattr(mod, "_backend_is_tpu", lambda: True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs",
                           "falcon-h1-34b-5l.json")) as f:
        config = json.load(f)
    sz = weights_falcon_h1.sizes(config)
    cfg = serve_falcon_h1.model_config(sz, config)

    class ShapesOnly:
        layer_specs = FalconH1ForCausalLM.layer_specs

        def __init__(self):
            self.cfg = cfg

        def decoder_params(self):
            def leaf(name, shape):
                return jax.ShapeDtypeStruct(
                    shape, jnp.float32 if name in
                    weights_falcon_h1.F32_LEAVES else jnp.bfloat16)

            table = (sz["vocab"], sz["hidden"])
            return {"wte": leaf("wte", table), "lm_head": leaf("lm_head", table),
                    "lnf_g": leaf("lnf_g", (sz["hidden"],)),
                    "blocks": [{n: leaf(n, s) for n, (s, _) in
                                weights_falcon_h1.leaf_shapes(sz).items()}
                               for _ in range(sz["layers"])]}

    eng = ServingEngine(ShapesOnly(), **config["engine"])
    assert eng.slab is not None and eng.ring is None
    assert eng.attention_paths() == dict.fromkeys(
        ("decode", "prefill", "ssm_step", "ssm_scan"), "kernel")
    return eng


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill_8"])
def test_state_program_keeps_pages_and_slab_in_place(monkeypatch, one_chip,
                                                     program):
    """The cell's own programs compile for the chip with all four paths as
    kernels (the paged ones at a GQA group of 5), fit its memory, and
    advance the slab where it lies: no copy, slice or re-layout the size of
    one layer's state (the kernels index the donated slab through scalar
    prefetch and return it aliased), temporaries far under it."""
    eng = _state_engine(monkeypatch)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params, bufs, key = jax.tree_util.tree_map(
        on_chip, (eng.params, eng._device_pool(), eng._key))
    s, mp = eng.max_slots, eng.max_pages
    rows = {"decode": s, "prefill": eng.chunk_tokens, "prefill_8": 8}[program]
    if program == "decode":
        fn, args = eng._decode_fn, (ints(s), ints(s), ints(s, mp), ints(s),
                                    key)
    else:
        fn, args = eng._prefill_fn, (ints(rows), ints(), ints(), ints(mp),
                                     ints(), key, ints())
    compiled = fn.lower(params, bufs, *args).compile()
    text = compiled.as_text()
    layers = len(eng.layers)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 2 * layers
    ssm = eng.slab.buffers["ssm"]
    layer = int(np.prod(ssm.shape[1:])) * ssm.dtype.itemsize
    assert layer > 128 << 20
    moved = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m and m.group(3) in _MOVES + ("transpose",):
            dtype, dims, op = m.groups()
            n = _BYTES.get(dtype, 4) * int(np.prod(
                [int(d) for d in dims.split(",") if d] or [1]))
            # float32 is the slab's type alone (weights are prefetched
            # whole, in bfloat16, and are no move of the slab)
            if dtype == "f32" and n >= layer // 8:
                moved.append(f"{op} {dtype}[{dims}]")
    assert not moved, f"slab-sized moves: {sorted(set(moved))}"
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer // 4, mem.temp_size_in_bytes
    # weights, pages and slab, once: what the chip has to hold (16 GB)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    assert mem.alias_size_in_bytes >= eng.slab.hbm_bytes()
