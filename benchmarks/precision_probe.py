#!/usr/bin/env python3
"""What an f32 matmul computes on this backend, at each precision.

The engine keeps float32 views that must agree with re-evaluation to
f32 accuracy, and the TPU's default precision for an f32 ``dot`` may
round its operands to bfloat16.  This probe measures, against a float64
NumPy reference, the max relative error (max |got − ref| / max |ref|) of

  * an XLA ``dot`` at each ``jax.lax.Precision`` (skinny and square);
  * a ``jnp.dot`` inside a Pallas kernel, at default and HIGHEST;
  * the engine's own apply kernel (``ops.rank_update_batched``);
  * a gram matrix ``XᵀX`` at HIGHEST, and ``jnp.linalg.inv``/``solve``
    of it, with and without ``default_matmul_precision("highest")``.

It is the evidence for the engine's ``precision=HIGHEST`` (PERF.md).
Run it on the chip, one process::

    PYTHONPATH=src python benchmarks/precision_probe.py          # n=4096
    PYTHONPATH=src JAX_PLATFORMS=cpu python benchmarks/precision_probe.py --n 256
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import ops

PRECISIONS = {"default": None, "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def rel_err(got, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def pallas_dot(a: jax.Array, b: jax.Array, precision) -> jax.Array:
    """``a @ b`` as one whole-array Pallas block."""
    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = jnp.dot(a_ref[...], b_ref[...], precision=precision,
                             preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((a.shape[0], b.shape[1]),
                                               jnp.float32),
        interpret=ops.interpret_mode())(a, b)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    n, k = args.n, 16
    rng = np.random.default_rng(args.seed)
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    out = {}

    def report(label: str, got, want) -> None:
        out[label] = rel_err(got, want)
        print(f"{label}: rel err {out[label]:.3e}", flush=True)

    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, k)).astype(np.float32)
    a64 = a.astype(np.float64)
    want = a64 @ b.astype(np.float64)
    for name, prec in PRECISIONS.items():
        f = jax.jit(lambda x, y, p=prec: jnp.dot(x, y, precision=p))
        report(f"xla dot ({n},{n})x({n},{k}) {name}", f(a, b), want)
    want = a64 @ a64
    for name in ("default", "highest"):
        f = jax.jit(lambda x, p=PRECISIONS[name]: jnp.dot(x, x, precision=p))
        report(f"xla dot ({n},{n})^2 {name}", f(a), want)

    m = min(n, 512)
    pa, pb = a[:m, :m], rng.standard_normal((m, 128)).astype(np.float32)
    want = pa.astype(np.float64) @ pb.astype(np.float64)
    for name in ("default", "highest"):
        report(f"pallas kernel dot ({m},{m})x({m},128) {name}",
               jax.jit(lambda x, y, p=PRECISIONS[name]: pallas_dot(x, y, p))(
                   pa, pb), want)

    u = rng.standard_normal((n, k)).astype(np.float32)
    v = rng.standard_normal((n, k)).astype(np.float32)
    want = a64 + u.astype(np.float64) @ v.astype(np.float64).T
    report(f"rank_update_batched kernel ({n},{n}) rank {k}",
           jax.jit(ops.rank_update_batched)(a, u, v), want)

    x = rng.standard_normal((2 * n, n)).astype(np.float32)
    x64 = x.astype(np.float64)
    g64 = x64.T @ x64
    g = jax.jit(lambda x: jnp.dot(x.T, x,
                                  precision=jax.lax.Precision.HIGHEST))(x)
    report(f"gram ({2 * n},{n}) highest", g, g64)
    g = np.asarray(g, np.float32)
    g64 = g.astype(np.float64)     # the inverse of what the engine holds
    y = rng.standard_normal((n, 1)).astype(np.float32)
    report(f"inv ({n},{n}) gram default", jax.jit(jnp.linalg.inv)(g),
           np.linalg.inv(g64))
    with jax.default_matmul_precision("highest"):
        report(f"inv ({n},{n}) gram default_matmul_precision(highest)",
               jax.jit(jnp.linalg.inv)(g), np.linalg.inv(g64))
    report(f"solve ({n},{n}) gram default",
           jax.jit(jnp.linalg.solve)(g, y),
           np.linalg.solve(g64, y.astype(np.float64)))
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "n": n, "rel_err": out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
