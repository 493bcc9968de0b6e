"""The plain re-evaluation every cell's views are compared with.

Straightforward ``jax.numpy`` in float32 with ``precision=HIGHEST``, run
on the final inputs that the benchmark rebuilds from the seed and its own
record of the updates.  It imports nothing of the program under test.

``control_matmul`` is the same product one step lower in precision: three
bfloat16 passes with float32 accumulation (``a_hi·b_hi + a_hi·b_lo +
a_lo·b_hi``), what ``precision=HIGH`` computes on a TPU, written out so
that it computes the same on every backend (the CPU ignores ``HIGH``).
Put in the program's place, it has to fail the comparison.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@jax.jit
def matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _split(x):
    """``x = hi + lo`` with hi its top 16 bits, exact in bfloat16.  Masking
    the bits, not rounding through bfloat16 and back, keeps XLA's excess
    precision from folding the round trip away (lo would then be 0)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


@jax.jit
def control_matmul(a, b):
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


@jax.jit
def rel_err(got, want):
    """``max |got − want| / max |want|``: one fused program, no
    view-sized temporaries left behind."""
    return (jnp.max(jnp.abs(got.astype(jnp.float32) - want))
            / jnp.max(jnp.abs(want)))


def powers(A, levels: int, mm=matmul):
    """``A, A², A⁴, … A^(2^levels)`` by repeated squaring, one at a time,
    so that the caller can compare and drop each before the next."""
    P = A
    yield P
    for _ in range(levels):
        P = mm(P, P)
        yield P


@jax.jit
def _add_rows(A, rows, deltas):
    return A.at[rows].add(deltas)


def apply_row_updates(A, rows, deltas):
    """``A`` with ``deltas[i]`` added to row ``rows[i]`` (rows distinct).
    The rows are padded with zero deltas to the next power of two, so
    that every count of rows shares one of a few compiled programs."""
    count = len(rows)
    if count == 0:
        return A
    size = 1 << (count - 1).bit_length()
    rows = np.pad(np.asarray(rows, np.int32), (0, size - count))
    deltas = np.pad(np.asarray(deltas, np.float32),
                    ((0, size - count), (0, 0)))
    return _add_rows(A, rows, deltas)
