"""Paper Fig. 3f: scalability with cluster size.

On this container the 'cluster' is the dry-run mesh: we report, from the
compiled artifacts, how the distributed-IVM trigger's collective bytes and
the re-evaluation matmul's collective bytes scale with mesh width — the
structural version of the paper's grid-size sweep (their finding: INCR is
far less sensitive to node count than REEVAL, because only O(nk) factors
move).  Executed numerically on an 8-device host mesh.
"""

from __future__ import annotations

import subprocess
import sys
import os
import textwrap

from .common import emit

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devs}"
import time
import jax, jax.numpy as jnp, numpy as np
from repro.core import IncrementalEngine
from repro.core.iterative import matrix_powers
from repro.dist.ivm_shard import build_distributed_trigger, distributed_reeval_matmul
from repro.roofline.hlo_walk import walk_hlo

n, k = 512, 8
rng = np.random.default_rng(0)
A = jnp.asarray(rng.normal(size=(n, n)) / 22, jnp.float32)
u = jnp.asarray(rng.normal(size=(n, 1)) * .1, jnp.float32)
v = jnp.asarray(rng.normal(size=(n, 1)) * .1, jnp.float32)

prog = matrix_powers(k=k, n=n, model="exp")
eng = IncrementalEngine(prog, {{"A": 1}})
eng.initialize({{"A": A}})
mesh = jax.make_mesh(({devs},), ("rows",))
trig = eng.compiled.triggers["A"]
fn = build_distributed_trigger(trig, eng.program, mesh, jit=False)
lowered = jax.jit(fn).lower(dict(eng.views), u, v)
w = walk_hlo(lowered.compile().as_text())
# reeval: one distributed n×n matmul per statement
mm = distributed_reeval_matmul(mesh, jit=False)
lw2 = jax.jit(mm).lower(A, A)
w2 = walk_hlo(lw2.compile().as_text())
print(f"RESULT {{w.collective_wire:.0f}} {{w2.collective_wire * {nstat}:.0f}}")
"""


def main():
    nstat = 3  # P2, P4, P8 statements
    for devs in (2, 4, 8):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "src")
        env.pop("XLA_FLAGS", None)
        # compile-only collective counts on virtual host devices: the
        # child must never reach for the chip a parent process may hold
        env["JAX_PLATFORMS"] = "cpu"
        res = subprocess.run(
            [sys.executable, "-c", SCRIPT.format(devs=devs, nstat=nstat)],
            env=env, capture_output=True, text=True, timeout=600)
        line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")]
        if not line:
            emit(f"fig3f_mesh{devs}", -1.0, "FAILED:" + res.stderr[-200:])
            continue
        incr_bytes, reeval_bytes = map(float, line[0].split()[1:])
        emit(f"fig3f_mesh{devs}_incr_collective_KB", incr_bytes / 1e3,
             f"reeval_KB={reeval_bytes/1e3:.0f};"
             f"ratio={reeval_bytes/max(incr_bytes,1):.1f}")


if __name__ == "__main__":
    main()
