"""The plain re-evaluation of ``bench/reference.py`` on row blocks.

For a deployment whose views no one chip holds whole: the same
``jax.numpy`` float32 products at ``precision=HIGHEST`` (and the same
control one precision step lower), with every operand and result placed
in row blocks by explicit shardings on the benchmark's own mesh, never by
what the compiler's propagation would pick and never through the program
under test.  A squaring gathers its right operand once and writes its own
rows, so the reference and the views held for the comparison fit side by
side on the chips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


@functools.lru_cache(maxsize=None)
def matmul(rows):
    """``reference.matmul`` with operands and result in ``rows``."""
    return jax.jit(lambda a, b: jnp.matmul(a, b,
                                           precision=reference.HIGHEST),
                   in_shardings=(rows, rows), out_shardings=rows)


@functools.lru_cache(maxsize=None)
def control_matmul(rows):
    """``reference.control_matmul`` with operands and result in ``rows``."""
    return jax.jit(reference.control_matmul, in_shardings=(rows, rows),
                   out_shardings=rows)


@functools.lru_cache(maxsize=None)
def _normal(rows, n: int, scale: float):
    return jax.jit(lambda key: jax.random.normal(key, (n, n), jnp.float32)
                   * jnp.float32(scale), out_shardings=rows)


def normal(key, n: int, scale: float, rows) -> jax.Array:
    """Standard normal ``(n, n)`` entries times ``scale``, made on the
    chips in ``rows``, each chip its own block."""
    return _normal(rows, n, scale)(key)


@functools.lru_cache(maxsize=None)
def _add_rows(rows):
    return jax.jit(lambda A, r, d: A.at[r].add(d), out_shardings=rows)


def apply_row_updates(A, rows_idx, deltas, rows):
    """``reference.apply_row_updates`` for ``A`` in ``rows``: the same
    zero-padding to a power of two, the result in ``rows``."""
    count = len(rows_idx)
    if count == 0:
        return A
    size = 1 << (count - 1).bit_length()
    rows_idx = np.pad(np.asarray(rows_idx, np.int32), (0, size - count))
    deltas = np.pad(np.asarray(deltas, np.float32),
                    ((0, size - count), (0, 0)))
    return _add_rows(rows)(A, rows_idx, deltas)
