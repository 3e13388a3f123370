"""The Retwis store of the paper's section V-D as a deployment of the
benchmark (see ``bench/spec.py`` for what a deployment module provides).

Each object is a map of ``slots`` versioned slots under ``max``, replicated
at every node of a circulant partial mesh; the schedule is the update-count
table of the Table II operations (``bench/generator.py``), the store's op
stream the program's ``workloads.versioned_slot_op`` over it, the plain
reference ``bench/reference.py`` and the byte count ``bench/roofline.py``.
A configuration names the keys ``objects``, ``nodes``, ``topology``
(``partial_mesh``), ``degree``, ``slots``, ``value`` (``max_int32``),
``weights_bytes``, ``op_stream`` (``versioned_slot_op``) and
``algorithm``.
"""

from __future__ import annotations

import numpy as np

from bench import generator, roofline
from bench import reference as plain

CONTROLS = plain.CONTROLS


def schedule(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """Update counts [T_active, N, B] int32 drawn from ``seed``."""
    return generator.update_counts(traffic, config["objects"],
                                   config["nodes"], seed % (1 << 64))


def weights(config: dict) -> np.ndarray:
    """Per-object element bytes [B]: the configuration's class weights,
    cycling over object ids."""
    w = np.asarray(config["weights_bytes"], np.float64)
    return w[np.arange(config["objects"]) % len(w)]


def store(config: dict, counts) -> tuple:
    """``(lattice, topology, StoreSpec)`` of the store over ``counts``; the
    per-object byte weights ride in ``StoreSpec.weights``."""
    from repro.core import value_lattices as vl
    from repro.core.lattice import MapLattice
    from repro.sync import StoreSpec, topology, workloads

    c = config
    if c["topology"] != "partial_mesh":
        raise ValueError(f"unknown topology {c['topology']!r}")
    if c["value"] != "max_int32":
        raise ValueError(f"unknown value lattice {c['value']!r}")
    if c["op_stream"] != "versioned_slot_op":
        raise ValueError(f"unknown op stream {c['op_stream']!r}")
    lattice = MapLattice(c["slots"], vl.max_int(), c["name"]).build()
    topo = topology.partial_mesh(c["nodes"], c["degree"])
    spec = StoreSpec(objects=c["objects"],
                     op_fn=workloads.versioned_slot_op(counts, c["slots"]),
                     weights=weights(c))
    return lattice, topo, spec


def reference(config: dict, counts, rounds: int, control=None) -> dict:
    """The plain reference's outputs (with ``control``: that control's) in
    the form ``check.compare`` reads: ``final_x`` [B, N, slots], ``acked``
    [B, slots], [B, T] ``tx``, ``mem``, ``cpu``, ``max_mem_node``,
    ``uniform`` and ``tx_bytes``."""
    c = config
    out = plain.simulate(counts, nodes=c["nodes"], degree=c["degree"],
                         slots=c["slots"], algorithm=c["algorithm"],
                         rounds=rounds, control=control)
    out["tx_bytes"] = out["tx"].astype(np.float64) * weights(c)[:, None]
    return out


def leq(a, b):
    """The max order of a slot."""
    return a <= b


def round_bytes(config: dict) -> int:
    """Bytes one sync round must read and write over the whole store."""
    c = config
    return roofline.round_bytes(c["algorithm"], c["objects"], c["nodes"],
                                c["degree"], c["slots"])
