"""Device guard and compile cache for scripts that run on the chip.

Nothing here runs at import: a script calls these after parsing its
arguments, so importing the package never touches device state or JAX's
configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# Published peaks of one chip, keyed by ``jax.Device.device_kind``
# (Google Cloud documentation, "TPU v5e"). A device missing from the
# table has no peaks, not a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def enable_compile_cache(root) -> Path:
    """Keep JAX's persistent compile cache in ``JAX_COMPILATION_CACHE_DIR``
    when that is set (JAX reads it itself), else in ``<root>/.jax_cache``:
    a fixed path, because the path is part of the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return Path(env)
    path = Path(root) / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path


def require_tpu():
    """The local TPU devices, or SystemExit when JAX finds none (no
    fallback to the CPU)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices "
                         f"{[d.device_kind for d in devs]}")
    return devs
