"""Roofline machinery: HLO walker trip-count correctness, collective
parsing with ring formulas, report math."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import RooflineReport
from repro.roofline.hlo_walk import walk_hlo, _ring_wire


def test_walker_counts_scan_trips():
    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    x = jnp.zeros((256, 256), jnp.float32)
    ws = jnp.zeros((12, 256, 256), jnp.float32)
    c = jax.jit(scanned).lower(x, ws).compile()
    w = walk_hlo(c.as_text())
    expect = 12 * 2 * 256 ** 3
    assert abs(w.flops - expect) / expect < 0.01
    # XLA's own analysis misses the trip count — that's why the walker exists
    ca = c.cost_analysis()
    assert ca["flops"] < w.flops / 5


def test_walker_nested_scan():
    def nested(x, ws):
        def outer(c, wgrp):
            def inner(c2, w):
                return c2 @ w, None
            c, _ = jax.lax.scan(inner, c, wgrp)
            return c, None
        out, _ = jax.lax.scan(outer, x, ws)
        return out

    x = jnp.zeros((128, 128), jnp.float32)
    ws = jnp.zeros((3, 4, 128, 128), jnp.float32)
    c = jax.jit(nested).lower(x, ws).compile()
    w = walk_hlo(c.as_text())
    expect = 12 * 2 * 128 ** 3
    assert abs(w.flops - expect) / expect < 0.02


def test_walker_bytes_reasonable_for_elementwise():
    def f(a, b):
        return a * 2.0 + b

    a = jnp.zeros((1024, 1024), jnp.float32)
    c = jax.jit(f).lower(a, a).compile()
    w = walk_hlo(c.as_text())
    # 2 reads + 1 write of 4MB each = 12MB, allow ~3× slack for copies
    assert 8e6 < w.bytes < 5e7


def test_ring_formulas():
    # all-gather:每 chip sends its shard to g-1 peers
    assert _ring_wire("all-gather", 0, 100, 4) == 300
    assert _ring_wire("all-reduce", 0, 100, 4) == pytest.approx(150)
    assert _ring_wire("reduce-scatter", 25, 100, 4) == 75
    assert _ring_wire("all-to-all", 0, 100, 4) == 75
    assert _ring_wire("collective-permute", 0, 100, 4) == 100
    assert _ring_wire("all-reduce", 0, 100, 1) == 0


def test_report_math():
    r = RooflineReport(
        arch="a", shape="s", mesh="16x16", chips=256,
        hlo_flops_per_chip=197e12 * 0.1,       # 100 ms compute
        hlo_bytes_per_chip=819e9 * 0.05,       # 50 ms memory
        collective_bytes_per_chip=50e9 * 0.2,  # 200 ms collective
        model_flops=256 * 197e12 * 0.08,       # 80 ms useful
        model_bytes=0.0)
    assert r.bottleneck == "collective"
    assert r.t_bound == pytest.approx(0.2)
    assert r.roofline_fraction == pytest.approx(0.4)
    assert r.useful_flops_ratio == pytest.approx(0.8)


def test_collective_parse_on_real_psum():
    """A jitted psum over 1 device lowers with no inter-chip collectives;
    the walker must not invent wire bytes (group size 1 → 0)."""
    def f(x):
        return x + 1

    c = jax.jit(f).lower(jnp.zeros((128,))).compile()
    w = walk_hlo(c.as_text())
    assert w.collective_wire == 0.0


# lines as a v5e 2x2 compile prints them: a tuple result whose layouts
# hold parentheses, and one all-gather repeated in two steps of an async
# collective fusion under one channel id
_TPU_HLO = """
  %all-reduce.13 = (f32[32768,2]{0,1:T(2,128)S(1)}, f32[2,2]{1,0:T(2,128)}) all-reduce(%fusion.2, %copy-done.34), channel_id=11, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.2.clone
  %all-gather.55 = f32[32768,2]{0,1:T(2,128)} all-gather(%param_0.63), channel_id=8, replica_groups=[1,4]<=[4], dimensions={0}
  %all-gather.57 = f32[32768,2]{0,1:T(2,128)S(1)} all-gather(%param_0.67), channel_id=8, replica_groups=[1,4]<=[4], dimensions={0}
"""


def test_collective_parse_tpu_tuple_layouts_and_channels():
    from repro.dist.ivm_shard import collective_bytes
    from repro.roofline.analysis import parse_collectives
    ops = parse_collectives(_TPU_HLO).ops
    assert [(o.kind, o.channel_id) for o in ops] == [
        ("all-reduce", 11), ("all-gather", 8), ("all-gather", 8)]
    assert ops[0].operand_bytes == 32768 * 2 * 4 + 2 * 2 * 4
    assert ops[0].group_size == 4
    # the repeated all-gather counts once
    assert collective_bytes(_TPU_HLO) == ops[0].operand_bytes + 32768 * 2 * 4


def test_model_flops_estimates_positive():
    from repro.configs import ARCHS, SHAPES, shape_applicable
    from repro.roofline.analysis import (model_bytes_estimate,
                                         model_flops_estimate)
    for arch, cfg in ARCHS.items():
        for shape in SHAPES.values():
            ok, _ = shape_applicable(cfg, shape)
            if not ok:
                continue
            assert model_flops_estimate(cfg, shape) > 0, (arch, shape.name)
            assert model_bytes_estimate(cfg, shape) > 0, (arch, shape.name)
