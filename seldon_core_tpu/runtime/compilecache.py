"""Persistent XLA compilation cache — shared boot helper for every serving
entrypoint (engine and unit microservice): restarts and rolling updates
reuse compiled executables instead of re-paying every XLA compile inside
the readiness-probe window.

Where the cache lives is decided from OUTSIDE the program:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this module
    sets no directory in code, so the operator's (or a test harness's)
    placement is the only one.
  * unset — one fixed directory inside the checkout, ``.xla_cache/`` next
    to the package (git-ignored).  Fixed because the path is part of the
    cache key: a home directory, temp name, pid or timestamp would never
    hit across machines or runs.
"""

from __future__ import annotations

import logging
import os

__all__ = ["compile_cache_dir", "enable_compile_cache"]

#: the in-checkout default: <checkout>/.xla_cache (the package's parent)
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".xla_cache",
)


def compile_cache_dir() -> str:
    """The directory the persistent cache uses under the rule above.
    Pure (never imports jax) so a parent process that must stay off the
    accelerator can tell where its engine children cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "") or _DEFAULT_DIR


def enable_compile_cache() -> bool:
    """Turn the persistent on-disk cache on.  Opt out with
    SELDON_COMPILE_CACHE=0.  Returns True when active; an unwritable
    directory logs a warning and serves uncached (every restart then
    pays full compiles).

    Outcomes land in ``seldon_tpu_compile_cache_events_total{outcome}``
    (utils/telemetry.py): enabled/disabled/error at boot, then hit/miss
    per compile via the jax.monitoring listener — the signal that says
    whether a restart re-pays XLA compiles or rides the cache.  The same
    listener maps backend-compile durations into the
    ``seldon_tpu_compile_seconds`` histogram, so hit/miss says WHETHER a
    compile was paid and the histogram says how much it cost."""
    from seldon_core_tpu.utils.telemetry import (
        RECORDER,
        install_compile_cache_listener,
    )

    if os.environ.get("SELDON_COMPILE_CACHE", "1") == "0":
        RECORDER.record_compile_cache("disabled")
        return False
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        try:
            os.makedirs(_DEFAULT_DIR, exist_ok=True)
        except OSError as e:
            logging.getLogger(__name__).warning(
                "compile cache disabled (%s: %s) — every restart pays "
                "full XLA compiles; set JAX_COMPILATION_CACHE_DIR to a "
                "writable directory", type(e).__name__, e,
            )
            RECORDER.record_compile_cache("error")
            return False
        import jax

        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    install_compile_cache_listener()
    RECORDER.record_compile_cache("enabled")
    return True
