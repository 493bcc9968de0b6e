"""Seeds, and the benchmark's own record of the updates it sent.

The reference rebuilds each final input from the seed and this record,
never from anything the program made.
"""

from __future__ import annotations

import numpy as np


def seed_words(seed: int, *stream: int) -> np.random.SeedSequence:
    """One independent stream per purpose, from any whole-number seed
    (NumPy takes big integers whole; JAX's key would keep 32 bits)."""
    return np.random.SeedSequence([int(seed) % 2 ** 64, *stream])


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, *stream))


def jax_key(seed: int, *stream: int):
    import jax
    return jax.random.key(int(seed_words(seed, *stream).generate_state(1)[0]))


def one_hot(n: int, row: int) -> np.ndarray:
    u = np.zeros((n, 1), np.float32)
    u[row, 0] = 1.0
    return u


class RowRecord:
    """Row deltas added to one input, summed per row in float64."""

    def __init__(self, n: int):
        self.n = n
        self.rows: dict[int, np.ndarray] = {}

    def add(self, rows, deltas) -> None:
        """Add ``deltas[i]`` to row ``rows[i]``."""
        for r, d in zip(rows, deltas):
            acc = self.rows.get(int(r))
            if acc is None:
                self.rows[int(r)] = np.array(d, np.float64)
            else:
                acc += d

    def arrays(self) -> tuple[np.ndarray, np.ndarray]:
        rows = np.array(sorted(self.rows), np.int32)
        deltas = np.stack([self.rows[r] for r in rows]) if len(rows) else \
            np.zeros((0, self.n))
        return rows, deltas.astype(np.float32)
