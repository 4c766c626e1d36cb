"""What a process that goes live on JAX needs to agree on with the
others: where compiled programs are kept, and how a device is named in
a result.

A chip belongs to one process at a time and every run on it may start
with nothing compiled, so each process that goes live on JAX shares one
cache directory. The directory is part of the cache key: it must be the
same path in every process and every run, never a temp dir, a pid or a
timestamp. ``JAX_COMPILATION_CACHE_DIR`` places it from outside; absent
that it is ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import sys

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def describe_device(dev=None) -> dict:
    """The stamp every result carries, as JAX reports it: the platform
    and kind of ``dev`` (default: the first device) and how many
    devices this process sees. Initialises the backend."""
    import jax
    dev = jax.devices()[0] if dev is None else dev
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def setup_compile_cache() -> str:
    """Point this process's JAX at the shared compile cache and return
    the directory. Call it where a process first goes live on JAX
    (driver scripts, the runtime worker's main, chip_smoke's children).

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing is set here. Otherwise the in-checkout default is applied
    through ``jax.config`` when jax is loaded, and through the
    variable itself when it is not yet: jax then reads it at import,
    this process never imports jax just to be configured, and the
    workers it spawns inherit the same directory."""
    path = os.environ.get(_ENV)
    if path:
        return path
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    else:
        os.environ[_ENV] = _DEFAULT
    return _DEFAULT
