"""Device acquisition, the compile-cache rule and the one lowering option.

One process owns the accelerator: the one that runs the ML threads (a
``WorkerNode``/``ValidatorNode`` caller, ``benchmarks/run.py``,
``chip_smoke.py``). :func:`acquire_devices` initialises the backend in that
process, in place — a second process probing the chip would take it from
the first. A backend that does not come up raises: the worker does not
start and the CLI exits non-zero. Nothing here continues on the CPU after
failing to get the accelerator; a CPU run is asked for explicitly with
``JAX_PLATFORMS=cpu``.

:func:`configure_compile_cache` is the one place that points JAX's
persistent compilation cache somewhere, and the one place that says how
much Python source an HLO operation carries.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path

# the persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset: one
# fixed directory inside the checkout (the path is part of the cache key,
# so a directory that moves never hits)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


@dataclass
class DeviceProbe:
    platform: str
    n_devices: int
    devices: list = field(default_factory=list)


def acquire_devices() -> DeviceProbe:
    """This process's local devices, as JAX reports them. Raises whatever
    backend initialisation raises."""
    import jax

    devs = jax.local_devices()
    return DeviceProbe(devs[0].platform, len(devs), devices=devs)


def device_hbm_bytes(dev) -> float:
    """One device's memory limit. On a TPU the runtime reports it; a TPU
    that does not is an error, never a guessed size. Backends without
    memory stats (the CPU) report 0 and the caller sizes from config."""
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if limit is None and dev.platform == "tpu":
        raise RuntimeError(
            f"{dev} reports no bytes_limit in memory_stats() — refusing to "
            "guess the HBM size of an accelerator"
        )
    return float(limit or 0.0)


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache under the one rule every
    entry point shares: ``JAX_COMPILATION_CACHE_DIR``, when set, is the
    directory (JAX reads it itself; no directory is set in code);
    otherwise :data:`COMPILE_CACHE_DIR`. Returns the directory in use.

    Programs are lowered without Python stack frames in their operations'
    metadata (``jax_traceback_in_locations_limit`` 0; JAX's default is ten
    frames an operation) unless ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT`` is
    set: the TPU profiler resolves the frames of every device event when a
    trace is stopped, a quarter of what stopping one costs (36.8 -> 27.3 s
    for 296,000 events of the serving step; PERF.md section 6, PR 27). The
    ``jax.named_scope`` paths stay in ``op_name``."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    # cache every program worth a second of compile; the serving step is
    # ~a minute on the chip, the page-management one-liners are not worth
    # a file each
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    if not os.environ.get("JAX_TRACEBACK_IN_LOCATIONS_LIMIT"):
        jax.config.update("jax_traceback_in_locations_limit", 0)
    return str(jax.config.jax_compilation_cache_dir)
