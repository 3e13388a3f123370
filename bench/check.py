"""The comparison that decides ``correct``.

Every experiment call of the window is compared, object by object, with
the plain reference of the cell's deployment (``deployments/<name>.py``)
run over the same schedule. States are pytrees whose leaves lead with the
object axis ([B, N, ...] final states, [B, ...] acknowledged joins), so a
lex-pair state is judged as a plain one is. Each number below counts
objects or elements that break one promise; each is exact, so each limit
is 0.

* ``objects_state_mismatch``: objects whose final replica states differ
  from the reference in any leaf;
* ``objects_metric_mismatch``: objects whose per-round ``tx``, ``mem``,
  ``cpu``, ``max_mem_node``, ``uniform`` or ``tx_bytes`` differ;
* ``objects_unconverged``: objects whose replicas still differ in any
  leaf after the quiet rounds (the convergence guarantee);
* ``acked_updates_missing``: (object, replica, element) triples whose
  state is not above the join of the updates the nodes applied, by the
  deployment's order ``leq`` (the guarantee that every acknowledged
  update reaches every replica).

A number is the largest over the calls compared.
"""

from __future__ import annotations

import jax
import numpy as np

LIMITS = {
    "objects_state_mismatch": 0,
    "objects_metric_mismatch": 0,
    "objects_unconverged": 0,
    "acked_updates_missing": 0,
}
METRICS = ("tx", "mem", "cpu", "max_mem_node", "uniform", "tx_bytes")


def call_outputs(res) -> dict:
    """The host arrays of one ``simulate_store`` result that are compared:
    final states (a pytree of [B, N, ...] leaves) and per-object [B, T]
    metrics."""
    out = {f: np.asarray(getattr(res, f)) for f in METRICS}
    out["final_x"] = jax.tree.map(np.asarray, res.final_x)
    return out


def _objects(masks) -> np.ndarray:
    """Objects [B] whose rows hold a True in any of the leaf ``masks``."""
    return np.logical_or.reduce(
        [np.any(m.reshape(m.shape[0], -1), axis=1) for m in masks])


def _shapes(tree) -> tuple:
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, [a.shape for a in leaves]


def compare_one(out: dict, ref: dict, leq) -> dict:
    """The numbers of one call's outputs against the reference; ``leq`` is
    the deployment's elementwise order."""
    fx, rx = out["final_x"], ref["final_x"]
    r_leaves = jax.tree.leaves(rx)
    if _shapes(fx) != _shapes(rx):
        objects = r_leaves[0].shape[0]
        return {"objects_state_mismatch": objects,
                "objects_metric_mismatch": objects,
                "objects_unconverged": objects,
                "acked_updates_missing": int(r_leaves[0].size)}
    f_leaves = jax.tree.leaves(fx)
    state_bad = _objects([f != r for f, r in zip(f_leaves, r_leaves)])
    metric_bad = np.zeros(state_bad.shape, bool)
    for m in METRICS:
        got, want = out[m], ref[m]
        metric_bad |= (np.any(got != want, axis=1) if got.shape == want.shape
                       else True)
    unconverged = _objects([a != a[:, :1] for a in f_leaves])
    acked = jax.tree.map(lambda a: a[:, None], ref["acked"])
    missing = ~np.asarray(leq(acked, fx))
    return {"objects_state_mismatch": int(state_bad.sum()),
            "objects_metric_mismatch": int(metric_bad.sum()),
            "objects_unconverged": int(unconverged.sum()),
            "acked_updates_missing": int(missing.sum())}


def compare(outs: list, ref: dict, leq) -> tuple:
    """``(numbers, bad_calls)``: each number the largest over ``outs``,
    and how many calls broke some limit."""
    numbers = {k: 0 for k in LIMITS}
    bad_calls = 0
    for out in outs:
        one = compare_one(out, ref, leq)
        bad_calls += any(one[k] > LIMITS[k] for k in LIMITS)
        for k, v in one.items():
            numbers[k] = max(numbers[k], v)
    return numbers, bad_calls


def within(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
