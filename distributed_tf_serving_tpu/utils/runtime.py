"""Process-level JAX setup shared by every entry point that compiles:
where the persistent compilation cache lives, and what the process reports
about the device it actually runs on.

Importing this module does not import jax (utils/config.py resolves paths
against CHECKOUT from jax-free processes)."""

from __future__ import annotations

import importlib.metadata
import os
import pathlib

# The checkout that holds this package. Paths the program creates at run
# time (the compile cache) resolve against it, never against the
# working directory: the cache key includes the path, so a cache that moves
# with `cd` never hits.
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
_DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"

_CACHE_EVENTS = "/jax/compilation_cache/"


class CompileCacheStats:
    """This process's persistent-cache traffic, counted from jax's own
    monitoring events. A miss is a compile request the cache could not
    answer; with the thresholds enable_compile_cache sets, every miss is
    also written back."""

    def __init__(self, directory: str | None):
        import jax

        self.directory = directory
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == _CACHE_EVENTS + "compile_requests_use_cache":
            self.requests += 1
        elif event == _CACHE_EVENTS + "cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {
            "dir": self.directory,
            "requests": self.requests,
            "hits": self.hits,
            "misses": self.requests - self.hits,
        }


def enable_compile_cache() -> CompileCacheStats:
    """Turn on jax's persistent compilation cache; call before the first jit.

    JAX_COMPILATION_CACHE_DIR, when set, places the cache from outside (jax
    reads the variable itself, so no directory is set here). Otherwise the
    cache goes to one fixed directory inside the checkout. The write
    thresholds drop to zero so the small buckets' sub-second compiles are
    cached too."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CompileCacheStats(jax.config.jax_compilation_cache_dir)


def _version(dist: str) -> str | None:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def describe_devices() -> dict:
    """What jax reports about this process's backend, for /monitoring's
    `runtime` block: an operator (and chip_smoke.py) reads here whether the
    server really sits on the accelerator — jax falls back to the CPU with
    only a warning when it finds none."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": _version("jaxlib"),
        "libtpu": _version("libtpu"),
    }
