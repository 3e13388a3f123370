"""Bytes one sync round of the store must move, from its shapes.

Counted from the work and not from any kernel's blocks or padding, so the
count holds for whatever implements the round. Per object and node, the
round reads the op stream's delta, the state and every buffer, and writes
the state and every buffer back: a BP+RR node keeps P+1 buffers (one per
neighbour origin plus local updates), a classic node one. Metric outputs
are left out; they are a few words per node. A share of the roofline
built on this count is a lower bound of the bandwidth actually used.
"""

from __future__ import annotations

BUFFERS = {"bprr": lambda degree: degree + 1, "classic": lambda degree: 1}


def round_bytes(algorithm: str, objects: int, nodes: int, degree: int,
                slots: int, itemsize: int = 4) -> int:
    """Bytes read and written by one round over the whole store."""
    if algorithm not in BUFFERS:
        raise ValueError(f"no byte count for {algorithm!r}; one of "
                         f"{sorted(BUFFERS)}")
    k = BUFFERS[algorithm](degree)
    plane = objects * nodes * slots * itemsize     # one [B, N, U] array
    reads = plane * (1 + 1 + k)                    # delta, state, buffers
    writes = plane * (1 + k)                       # state, buffers
    return reads + writes
