"""Cross-lower every Pallas entry for platform ``tpu`` from the CPU.

``interpret=True`` (what every other kernel test runs) never meets the
Mosaic lowering, so a kernel can be exact on the CPU and still not exist
on the chip: the single-query decode kernel and the ``sbnd`` flash entry
were both in that state (ISSUE 21).  Lowering with ``interpret=False`` for
``lowering_platforms=("tpu",)`` needs no chip and takes seconds.  It is
LOWERING only — Mosaic's layout and VMEM checks run inside libtpu at
compile time, which ``python chip_smoke.py`` exercises on the chip with
the same cases.
"""

import os

import jax
import pytest

import chip_smoke
from paddle_tpu.utils import compile_cache

SBND_REFUSAL = (
    "the TPU lowering refuses a squeezed second-to-last block dimension: "
    "flash._smajor_specs builds (block, None, d) blocks over the (S, B, "
    "H*D) array — ValueError: ... last two dimensions of your block shape "
    "... (ROADMAP D4: seq_major cannot run on the chip as written)")


def _params():
    for name in chip_smoke.KERNEL_CASES:
        marks = [pytest.mark.xfail(strict=True, raises=ValueError,
                                   reason=SBND_REFUSAL)] \
            if name.startswith("flash_sbnd") else []
        yield pytest.param(name, marks=marks)


@pytest.mark.parametrize("name", _params())
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
