"""The comparison that decides ``correct``.

Every experiment call of the window is compared, object by object, with
the plain reference (``reference.py``) run over the same update counts.
Each number below counts objects or slots that break one promise; each
is exact, so each limit is 0.

* ``objects_state_mismatch``: objects whose final replica states differ
  from the reference;
* ``objects_metric_mismatch``: objects whose per-round ``tx``, ``mem``,
  ``cpu``, ``max_mem_node``, ``uniform`` or ``tx_bytes`` differ;
* ``objects_unconverged``: objects whose replicas still differ after the
  quiet rounds (the convergence guarantee);
* ``acked_updates_missing``: (object, replica, slot) triples that hold
  less than the join of the updates the nodes applied (the guarantee that
  every acknowledged update reaches every replica).

A number is the largest over the calls compared.
"""

from __future__ import annotations

import numpy as np

LIMITS = {
    "objects_state_mismatch": 0,
    "objects_metric_mismatch": 0,
    "objects_unconverged": 0,
    "acked_updates_missing": 0,
}
METRICS = ("tx", "mem", "cpu", "max_mem_node", "uniform", "tx_bytes")


def call_outputs(res, weights: np.ndarray) -> dict:
    """The host arrays of one ``simulate_store`` result that are compared:
    final states [B, N, U] and per-object [B, T] metrics."""
    out = {f: np.asarray(getattr(res, f)) for f in METRICS[:-1]}
    out["final_x"] = np.asarray(res.final_x)
    out["tx_bytes"] = np.asarray(res.tx_bytes)
    out["weights"] = weights
    return out


def compare_one(out: dict, ref: dict) -> dict:
    """The numbers of one call's outputs against the reference."""
    fx, rx = out["final_x"], ref["final_x"]
    if fx.shape != rx.shape:
        objects = rx.shape[0]
        return {"objects_state_mismatch": objects,
                "objects_metric_mismatch": objects,
                "objects_unconverged": objects,
                "acked_updates_missing": int(rx.shape[0] * rx.shape[1]
                                             * rx.shape[2])}
    state_bad = np.any(fx != rx, axis=(1, 2))
    ref_bytes = ref["tx"].astype(np.float64) * out["weights"][:, None]
    metric_bad = np.zeros(fx.shape[0], bool)
    for f in METRICS:
        want = ref_bytes if f == "tx_bytes" else ref[f]
        got = out[f]
        metric_bad |= (np.any(got != want, axis=1) if got.shape == want.shape
                       else True)
    unconverged = np.any(fx != fx[:, :1], axis=(1, 2))
    missing = fx < ref["acked"][:, None, :]
    return {"objects_state_mismatch": int(state_bad.sum()),
            "objects_metric_mismatch": int(metric_bad.sum()),
            "objects_unconverged": int(unconverged.sum()),
            "acked_updates_missing": int(missing.sum())}


def compare(outs: list, ref: dict) -> tuple:
    """``(numbers, bad_calls)``: each number the largest over ``outs``,
    and how many calls broke some limit."""
    numbers = {k: 0 for k in LIMITS}
    bad_calls = 0
    for out in outs:
        one = compare_one(out, ref)
        bad_calls += any(one[k] > LIMITS[k] for k in LIMITS)
        for k, v in one.items():
            numbers[k] = max(numbers[k], v)
    return numbers, bad_calls


def within(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
