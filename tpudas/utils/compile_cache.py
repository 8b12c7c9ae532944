"""Persistent XLA compilation cache.

The engine's first window pays the jit compile (seconds to tens of
seconds on a TPU); a deployment that restarts its process per polling
round pays it at every start.  JAX's persistent compilation cache keys
compiled executables by (HLO, compile options, backend) and reuses them
across processes, cutting warm restarts to cache-hit latency.

Placement is decided from outside the program:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  here configures another directory;
- otherwise the fixed ``<checkout>/.jax_cache`` (git-ignored).  The
  path is part of JAX's cache key, so it must not move between runs.

The entry points (``LFProc``, the fleet, ``bench.py``,
``chip_smoke.py``) turn the cache on by default through
:func:`enable_compile_cache`; ``JAX_ENABLE_COMPILATION_CACHE=false``
turns it off.
"""

from __future__ import annotations

import os

from tpudas.obs.registry import get_registry

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_ENABLED = False
_LISTENER_INSTALLED = False


def _install_metrics_listener() -> None:
    """Mirror JAX's persistent-cache monitoring events
    (``/jax/compilation_cache/cache_hits`` / ``cache_misses``) into the
    obs registry so operators can see warm-restart behavior in
    ``metrics.prom``.  Private-API tolerant: any failure leaves the
    cache working, just uncounted."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    try:
        from jax._src import monitoring

        def _on_event(event, **kwargs):
            if "/jax/compilation_cache/" not in event:
                return
            if event.endswith("cache_hits"):
                get_registry().counter(
                    "tpudas_compile_cache_hits_total",
                    "persistent XLA compilation cache hits",
                ).inc()
            elif event.endswith("cache_misses"):
                get_registry().counter(
                    "tpudas_compile_cache_misses_total",
                    "persistent XLA compilation cache misses",
                ).inc()

        monitoring.register_event_listener(_on_event)
        _LISTENER_INSTALLED = True
    except Exception:  # pragma: no cover - private-API drift
        pass


def cache_dir() -> str:
    """The directory the placement rule selects (module doc)."""
    return os.environ.get(ENV_DIR) or CHECKOUT_CACHE


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`cache_dir` and return
    it.  Idempotent; must precede the first jit compile to benefit
    it."""
    global _ENABLED
    import jax

    path = cache_dir()
    if not _ENABLED and not os.environ.get(ENV_DIR):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # the cache singleton binds to the directory it first
        # initialized with; re-pointing the config alone leaves writes
        # going wherever the singleton was born
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
    # JAX's default min-compile-time threshold (1 s) already skips the
    # small host-side jits while caching the window kernels
    _install_metrics_listener()
    reg = get_registry()
    reg.gauge(
        "tpudas_compile_cache_enabled",
        "1 when the persistent XLA compilation cache is active",
    ).set(1)
    try:
        reg.gauge(
            "tpudas_compile_cache_entries",
            "files in the persistent compilation cache directory at "
            "enable time",
        ).set(len(os.listdir(path)))
    except OSError:
        pass
    _ENABLED = True
    return path
