"""Where the program runs: the one place that decides it, and says so.

JAX picks a platform silently (TPU when it finds one, else CPU), the Pallas
kernels pick compiled-vs-interpreter from that, and the persistent compile
cache needs a directory that does not move (its path is part of the cache
key). Each of those decisions lives here once, so a role's first log line
and ``chip_smoke.py`` report the same facts the kernels acted on.
"""
from __future__ import annotations

import contextlib
import contextvars
import os
from typing import Dict, Iterator, Optional

import jax

# <checkout>/.jax_cache — derived from the package's own location so two
# processes started from the same tree always agree on it
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. ``JAX_COMPILATION_CACHE_DIR`` wins untouched (jax reads it
    itself); otherwise the cache lives in ``<checkout>/.jax_cache``. Call
    before the first compile of every entry point."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def pin_cpu() -> None:
    """Keep this process off the accelerator. A chip belongs to one process
    at a time; roles that never compute on the device (aux, coordinator,
    dht_node, gateway) call this before any backend exists so that on a TPU
    host they cannot take the chip a trainer is waiting for."""
    jax.config.update("jax_platforms", "cpu")


_LOWERING_FOR_TPU = contextvars.ContextVar("lowering_for_tpu", default=False)


@contextlib.contextmanager
def lowering_for_tpu() -> Iterator[None]:
    """Trace the Pallas ops as Mosaic kernels although this process computes
    elsewhere: for programs that are lowered here and compiled for an absent
    TPU (``tools/tpu_aot.py``), never for ones that run here."""
    token = _LOWERING_FOR_TPU.set(True)
    try:
        yield
    finally:
        _LOWERING_FOR_TPU.reset(token)


def pallas_interpret() -> bool:
    """Kernel mode for every Pallas op in ``dedloc_tpu/ops``: compiled by
    Mosaic on TPU, the Pallas interpreter (plain jnp ops) anywhere else —
    which exists so CPU tests and the virtual mesh run the same kernel
    code, never as a stand-in for a chip measurement."""
    return jax.default_backend() != "tpu" and not _LOWERING_FOR_TPU.get()


def describe_backend() -> Dict[str, object]:
    """Platform facts as JAX reports them, plus the kernel mode that
    follows from them (initialises the backend)."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "kernel_mode": "interpret" if pallas_interpret() else "compiled",
    }


def hbm_bytes_in_use() -> Optional[int]:
    """Device bytes_in_use via PJRT memory_stats (None off-TPU/unsupported)."""
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        return int(stats.get("bytes_in_use", 0)) or None
    except Exception:  # noqa: BLE001 — telemetry must never kill training
        return None
