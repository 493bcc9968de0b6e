"""The main-path Pallas kernels compile for one TPU v5e chip.

Nothing runs: each test lowers a kernel for a *described* v5e chip and
asks the TPU compiler for the executable, which refuses what interpret
mode cannot — blocks not aligned to the (8, 128) tile, more VMEM than a
kernel may use.  The topology is described inside a fixture, so only the
test worker that runs this file loads the TPU compiler.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.rank_update_rows import rank_update_rows_pallas

WIDE = 16384


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_cache_writes():
    # a compile for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("t,k", [(1, 1), (1, 16), (16, 1)])
def test_rank_update_batched_compiles(one_chip, t, k):
    text = _compiled_text(
        lambda m, u, v: ops.rank_update_batched(m, u, v, interpret=False),
        one_chip, (WIDE, WIDE), (t, WIDE, k), (t, WIDE, k))
    assert "tpu_custom_call" in text


def test_rank_update_rows_compiles(one_chip):
    slab = ops._pick_block(WIDE, 256)
    bn = ops.rank_update_rows_block(slab, WIDE, 1)
    assert bn is not None
    ids = jnp.arange(4, dtype=jnp.int32)
    text = _compiled_text(
        lambda m, u, v: rank_update_rows_pallas(m, ids, u, v, slab=slab,
                                                bn=bn, interpret=False),
        one_chip, (WIDE, WIDE), (WIDE, 1), (WIDE, 1))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [1, 8])
def test_dual_matmul_compiles(one_chip, k):
    n = 8192
    assert ops.dual_matmul_blocks(n, n, k) is not None
    text = _compiled_text(
        lambda a, u, v: ops.dual_matmul(a, u, v, interpret=False),
        one_chip, (n, n), (n, k), (n, k))
    assert "tpu_custom_call" in text


def test_view_1000_wide_compiles(one_chip):
    """No multiple of 128 divides 1000: the lane block is the whole
    width, and the row block shrinks to fit VMEM instead."""
    assert ops.rank_update_blocks(4096, 1000, 1, 2) is not None
    text = _compiled_text(
        lambda m, u, v: ops.rank_update_batched(m, u, v, interpret=False),
        one_chip, (4096, 1000), (4096, 2), (1000, 2))
    assert "tpu_custom_call" in text


def test_pallas_trigger_firing_compiles(one_chip, monkeypatch):
    """A whole matrix-powers firing with the Pallas apply: every view's
    rank-k update is a kernel, none takes the XLA reference."""
    from repro.core.codegen import build_trigger_fn
    from repro.core.compiler import compile_program
    from repro.core.iterative import matrix_powers
    monkeypatch.setattr(ops, "interpret_mode", lambda interpret=None: False)
    n = 2048
    compiled = compile_program(matrix_powers(k=8, n=n), {"A": 1})
    trig = compiled.triggers["A"]
    run = build_trigger_fn(trig, compiled.program, jit=False,
                           apply_backend="pallas")
    names = ["A", "P2", "P4", "P8"]

    def fire(u, v, *views):
        return run(dict(zip(names, views)), u, v)

    text = _compiled_text(fire, one_chip, (n, 1), (n, 1),
                          *[(n, n)] * len(names))
    assert text.count("tpu_custom_call") >= len(names)
    assert run.fallbacks == {}


@pytest.mark.parametrize("k", [1, 8])
def test_apply_project_compiles(one_chip, k):
    assert ops.apply_project_blocks(WIDE, WIDE, k, k, k) is not None
    text = _compiled_text(
        lambda p, u, v, x, y: ops.apply_project(p, u, v, x, y,
                                                interpret=False),
        one_chip, (WIDE, WIDE), *[(WIDE, k)] * 4)
    assert "tpu_custom_call" in text


def test_fused_trigger_firing_compiles(one_chip, monkeypatch):
    """A rank-1 matrix-powers firing at n=16384 as a TPU stages it: one
    apply-and-project kernel per view the selection picks, and XLA's
    apply for the top view."""
    from repro.core import codegen
    from repro.core.compiler import compile_program
    from repro.core.iterative import matrix_powers
    monkeypatch.setattr(codegen, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "interpret_mode", lambda interpret=None: False)
    compiled = compile_program(matrix_powers(k=16, n=WIDE), {"A": 1})
    run = codegen.build_trigger_fn(compiled.triggers["A"], compiled.program,
                                   jit=False)
    assert {"P2", "P4", "P8"} <= set(run.fused_views)
    names = ["A", "P2", "P4", "P8", "P16"]

    def fire(u, v, *views):
        return run(dict(zip(names, views)), u, v)

    text = _compiled_text(fire, one_chip, (WIDE, 1), (WIDE, 1),
                          *[(WIDE, WIDE)] * len(names))
    assert text.count("tpu_custom_call") == len(run.fused_views)
