"""The work one iteration requires, counted from the algorithm.

These count what the multi-signal iteration needs, not what an
implementation computes: Find Winners compares the ``m`` valid signals
with the ``n`` active units only, and the Update touches only the
surviving signals' winners and their neighbours. Every implementation
is judged on the same counts. All arguments are plain numbers; every
value is in float32 words of 4 bytes.
"""
from __future__ import annotations

WORD = 4


def m_schedule(n_active: int, buffer: int, min_m: int = 4) -> int:
    """Valid signals of an iteration: the smallest power of two above
    ``n_active``, within [min_m, buffer]."""
    m = 1 << max(int(n_active), 0).bit_length()
    return max(min(m, buffer), min(min_m, buffer))


def find_winners(m: int, n: int, dim: int) -> tuple[float, float]:
    """(flops, bytes) of the top-2 search of m signals among n units:
    one squared distance per pair (a dot product of 2*dim flops, and
    3 more for |x|^2 - 2 x.w + |w|^2); each signal and unit read once,
    and (winner, second, distance) written per signal."""
    flops = m * n * (2 * dim + 3)
    nbytes = WORD * (m * dim + n * (dim + 1) + 3 * m)
    return float(flops), float(nbytes)


def update(survivors: float, degree: float, dim: int) -> tuple[float, float]:
    """(flops, bytes) of the Update for ``survivors`` signals that hold
    a winner, whose winners have ``degree`` neighbours on average: the
    winner and each neighbour move toward the signal (3*dim flops) and
    lower their firing counter (2 flops); every edge at the winner ages
    in both of its rows (1 flop each). Each touched unit's vector and
    counter are read and written, and each aged edge's two age words."""
    touched = survivors * (1.0 + degree)
    flops = touched * (3 * dim + 2) + survivors * 2 * degree
    nbytes = WORD * (touched * 2 * (dim + 1) + survivors * 2 * 2 * degree)
    return float(flops), float(nbytes)
