"""Matrix powers (LINVIEW §7): ``P_1 = A``, ``P_2 = P_1²`` … ``P_k``.

What the benchmark needs of one program: its views, the program under
test built from the configuration, its input made on the device from the
seed, the update deltas, and the readings of the plain reference.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

INPUT = "A"


def levels(cfg: dict) -> int:
    return int(cfg["k"]).bit_length() - 1


def view_names(cfg: dict) -> list[str]:
    return [INPUT] + [f"P{2 ** i}" for i in range(1, levels(cfg) + 1)]


def build_program(cfg: dict):
    from repro.core.iterative import matrix_powers
    return matrix_powers(k=int(cfg["k"]), n=int(cfg["n"]))


@partial(jax.jit, static_argnums=(1, 2))
def _normal(key, n: int, scale: float):
    return jax.random.normal(key, (n, n), jnp.float32) * jnp.float32(scale)


def synthesize(cfg: dict, key) -> jax.Array:
    """A on the device: standard normal entries scaled by
    ``spectral_scale / √n`` (circular law: spectral radius about
    ``spectral_scale``, so the powers stay bounded)."""
    n = int(cfg["n"])
    return _normal(key, n, float(cfg["spectral_scale"]) / math.sqrt(n))


def deltas(cfg: dict, rng: np.random.Generator, count: int,
           scale: float) -> np.ndarray:
    """``count`` row deltas, standard normal scaled by ``scale / √n`` (so
    each has a norm of about ``scale``), as the rows of a float32
    ``(count, n)`` array."""
    n = int(cfg["n"])
    d = rng.standard_normal((count, n), dtype=np.float32)
    d *= np.float32(scale / math.sqrt(n))
    return d


def readings(cfg: dict, A_final, views: dict) -> dict[str, float]:
    """Each view's ``max |view − reference| / max |reference|``."""
    out = {}
    for name, P in zip(view_names(cfg), reference.powers(A_final,
                                                         levels(cfg))):
        out[name] = float(reference.rel_err(views[name], P))
    return out


def control_readings(cfg: dict, A_final) -> dict[str, float]:
    """The same readings with the control (one precision step lower) put
    in the program's place."""
    out = {}
    pairs = zip(view_names(cfg),
                reference.powers(A_final, levels(cfg)),
                reference.powers(A_final, levels(cfg),
                                 mm=reference.control_matmul))
    for name, P, C in pairs:
        out[name] = float(reference.rel_err(C, P))
    return out
