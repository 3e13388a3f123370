"""Shared tiling helpers for the CRDT Pallas kernels.

TPU adaptation (DESIGN.md §3): lattice states are dense arrays; the paper's
hot operations (join, Δ-extraction, per-neighbor buffer folds) are
elementwise selects/maxes plus small reductions — VPU work. We tile the
(flattened) universe into (8k, 128m)-aligned 2D blocks so each block maps
onto VPU sublanes×lanes and streams HBM→VMEM once.
"""

from __future__ import annotations

import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Default VMEM tile: 512×1024 int32 = 2 MiB per operand — comfortably inside
# the ~16 MiB/core VMEM budget with 2-3 operands + outputs double-buffered.
DEFAULT_BLOCK = (512, 1024)
LANE = 128
SUBLANE = 8

# Kernel view of boolean states: {0, 1} words under max (≡ or). TPU
# tiles have no bool layout, and v5e's Mosaic refuses 8-bit compares, so
# the view is 32-bit.
BOOL_VIEW = jnp.int32

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def interpret_default() -> bool:
    """Run kernels in interpret mode off-TPU (this container is CPU-only).

    ``REPRO_INTERPRET=1`` forces interpret mode even on TPU (debugging);
    ``REPRO_INTERPRET=0`` forces compiled Pallas even off-TPU (fails loudly
    where Mosaic is unavailable — useful to verify a TPU deployment really
    left interpret mode). Unset/empty keeps the backend-derived default.
    """
    env = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    if env in _TRUE:
        return True
    if env in _FALSE:
        return False
    return jax.default_backend() != "tpu"


def pallas_call(kernel, **kw):
    """``pl.pallas_call`` traced with 32-bit defaults.

    The simulator traces its scan under ``jax.enable_x64`` so the metric
    accumulators are int64 (DESIGN.md §10). Traced there, a kernel's grid
    indices and index maps become 64-bit, which Mosaic cannot lower
    (``failed to legalize operation 'func.return'``). Every kernel operand
    and output carries an explicit 32-bit-or-narrower dtype, so tracing the
    call with x64 off changes no value — only the index arithmetic's width.
    """
    call = pl.pallas_call(kernel, **kw)

    def run(*args):
        with jax.enable_x64(False):
            return call(*args)

    return run


def backend_key() -> str:
    """Autotune cache namespace: the compilation target actually timed —
    interpret-mode Pallas (XLA-emulated) has a different cost surface than
    compiled Mosaic on the same machine."""
    base = jax.default_backend()
    return f"{base}-interpret" if interpret_default() else base


# -- block-size autotuner (DESIGN.md §17) -------------------------------------
#
# Tile geometry is a per-backend tradeoff: on TPU, bigger tiles amortize
# grid overhead until VMEM pressure bites; under CPU interpret mode each
# grid step is a Python-driven emulated launch, so fewer/wider tiles win by
# a large margin. Rather than hardcode one (bm, bn) per kernel family, the
# wrappers enumerate a few candidates and ask ``tuned_block`` — which
# resolves, in order: process memo → on-disk cache → (only when
# REPRO_AUTOTUNE=1) timing each candidate on the live shapes.
#
# Modes (REPRO_AUTOTUNE):
#   unset  → "cache": use a cached winner if one exists, else the heuristic
#            default — never spends time measuring (tests stay fast and
#            deterministic).
#   1/on   → "tune": cache miss triggers measurement; the winner is persisted
#            (benchmarks enable this so BENCH_engine records tuned configs).
#   0/off  → "off": ignore the cache, always the heuristic default.
#
# Cache keys: family|backend|kind|degree/slot-count|layout|pow2 shape
# buckets — coarse enough that one measurement covers a family of nearby
# shapes, fine enough that CPU-interpret and TPU never share a winner.

_TUNE_MEM: dict = {}


def autotune_mode() -> str:
    v = os.environ.get("REPRO_AUTOTUNE", "").strip().lower()
    if v in _FALSE:
        return "off"
    if v in _TRUE or v == "tune":
        return "tune"
    return "cache"


def autotune_cache_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE", "").strip()
    if env:
        return pathlib.Path(env)
    root = os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache"))
    return pathlib.Path(root) / "repro-crdt" / "autotune.json"


def shape_bucket(n: int) -> int:
    """Next power of two ≥ n (≥ 1): the shape granularity of cache keys."""
    return 1 << max(0, int(n - 1).bit_length())


def _load_tune_cache(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        # missing or corrupt cache → retune/default; never crash the caller
        return {}


def _store_tune_cache(path: pathlib.Path, cache: dict) -> None:
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                       # read-only FS: tuning still works, untracked


def tuned_block(family: str, key_parts, candidates, bench=None, *,
                mode: str | None = None, timer=time.perf_counter,
                reps: int = 2, warmup: int = 1, cache_path=None):
    """Resolve the tile config for one kernel-family call site.

    ``candidates``: non-empty list of config tuples, first = heuristic
    default. ``bench(config)``: runs the kernel once with that config
    (including ``block_until_ready``); only invoked in "tune" mode.
    ``timer``/``reps``/``warmup``/``cache_path``/``mode`` are injectable
    for tests. Returns ``(config, source)`` with source one of
    "default" | "cache" | "tuned". A candidate whose bench raises is
    skipped (e.g. a tile too large for compiled Mosaic).
    """
    candidates = [tuple(c) for c in candidates]
    default = candidates[0]
    mode = autotune_mode() if mode is None else mode
    if mode == "off" or len(candidates) == 1:
        return default, "default"
    path = pathlib.Path(cache_path) if cache_path is not None \
        else autotune_cache_path()
    key = "|".join((family,) + tuple(str(p) for p in key_parts))
    memo_key = (str(path), key)
    if memo_key in _TUNE_MEM:
        return _TUNE_MEM[memo_key], "cache"
    cache = _load_tune_cache(path)
    ent = cache.get(key)
    if isinstance(ent, dict):
        try:
            cfg = tuple(int(v) for v in ent["config"])
        except (KeyError, TypeError, ValueError):
            cfg = None             # corrupt entry → fall through
        if cfg in candidates:
            _TUNE_MEM[memo_key] = cfg
            return cfg, "cache"
    if mode != "tune" or bench is None:
        return default, "default"
    best, best_t = default, float("inf")
    timings = {}
    for cand in candidates:
        try:
            for _ in range(warmup):
                bench(cand)
            ts = []
            for _ in range(reps):
                t0 = timer()
                bench(cand)
                ts.append(timer() - t0)
        except Exception:          # noqa: BLE001 — unbuildable candidate
            continue
        t = min(ts)
        timings[str(list(cand))] = t
        if t < best_t:
            best, best_t = cand, t
    cache[key] = {"config": list(best), "timings_s": timings}
    _store_tune_cache(path, cache)
    _TUNE_MEM[memo_key] = best
    return best, "tuned"


def pad_to_2d(x: jnp.ndarray, block=DEFAULT_BLOCK):
    """Flatten trailing axes to 1D, pad, reshape to [M, N] tiles.

    Returns (x2d, orig_shape, valid_len). Padding value 0 is ⊥ for every
    value lattice we use (max over ℕ, or over bool, bit-or over packed words),
    so padded slots never contribute to joins/sizes.
    """
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    bm, bn = block
    cols = bn
    rows = -(-n // cols)
    rows_pad = -(-rows // bm) * bm
    total = rows_pad * cols
    flat = jnp.pad(flat, (0, total - n))
    return flat.reshape(rows_pad, cols), shape, n


def unpad_from_2d(x2d: jnp.ndarray, shape, n):
    return x2d.reshape(-1)[:n].reshape(shape)


def grid_for(shape_2d, block=DEFAULT_BLOCK):
    m, n = shape_2d
    bm, bn = block
    return (-(-m // bm), -(-n // bn))
