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

import hashlib
import json
import logging
import os
from typing import Dict, Set

__all__ = [
    "compile_cache_dir",
    "enable_compile_cache",
    "program_record_path",
    "read_program_record",
    "write_program_record",
]

logger = logging.getLogger(__name__)

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
            logger.warning(
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


# ---------------------------------------------------------------------------
# The program record: which shapes of the scheduler's paged programs a
# deployment dispatched, kept in the cache directory so that the next boot
# loads exactly those before its first request (runtime/genserver.py
# ``_load_programs``).  A HINT that holds no executable: JAX's persistent
# cache stays the only store of compiled code and its key the only thing
# that decides whether code is current, so a record that is stale, from
# other source, corrupt or half-written costs at worst a compile at boot
# and never a wrong program.
# ---------------------------------------------------------------------------

_RECORD_VERSION = 1
#: program kind -> entries of one shape: (rows, chunk, blocks) / (rows, blocks)
_RECORD_ARITY = {"prefill": 3, "decode": 2}


def program_record_path(identity: str) -> str:
    """Where the record of the deployment ``identity`` lives: one file in
    the directory JAX's persistent cache is configured with NOW
    (``enable_compile_cache()`` or ``JAX_COMPILATION_CACHE_DIR`` set it),
    named by a digest of the identity.  '' where no persistent cache is on
    (``SELDON_COMPILE_CACHE=0``, no directory): then nothing is recorded
    and nothing is loaded at boot."""
    if os.environ.get("SELDON_COMPILE_CACHE", "1") == "0":
        return ""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir or not jax.config.jax_enable_compilation_cache:
        return ""
    digest = hashlib.sha256(identity.encode()).hexdigest()[:16]
    return os.path.join(cache_dir, f"genserver-programs-{digest}.json")


def read_program_record(path: str, identity: str) -> Dict[str, Set[tuple]]:
    """The shapes the record at ``path`` lists, by program kind.  A file
    that is absent reads as empty in silence; one that is truncated, not
    JSON, of another version or identity, or lists anything but tuples of
    positive integers reads as empty with one warning."""
    empty: Dict[str, Set[tuple]] = {kind: set() for kind in _RECORD_ARITY}
    try:
        with open(path) as f:
            doc = json.load(f)
        if (doc["version"], doc["identity"]) != (_RECORD_VERSION, identity):
            raise ValueError("another version or deployment")
        out = {kind: {tuple(shape) for shape in doc[kind]}
               for kind in _RECORD_ARITY}
        for kind, arity in _RECORD_ARITY.items():
            for shape in out[kind]:
                if len(shape) != arity or not all(
                        type(n) is int and n > 0 for n in shape):
                    raise ValueError(f"{kind} shape {shape!r}")
        return out
    except FileNotFoundError:
        return empty
    except (OSError, ValueError, KeyError, TypeError) as e:
        logger.warning("program record %s ignored (%s: %s): this boot loads "
                       "no program ahead of its first request",
                       path, type(e).__name__, e)
        return empty


def write_program_record(path: str, identity: str,
                         programs: Dict[str, Set[tuple]]) -> bool:
    """Write the record whole, atomically (a reader sees the old file or
    the new one).  False, with a warning, where the directory cannot be
    written: the caller stops recording."""
    doc = {"version": _RECORD_VERSION, "identity": identity,
           **{kind: sorted(programs[kind]) for kind in _RECORD_ARITY}}
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return True
    except OSError as e:
        logger.warning("program record %s not written (%s: %s)",
                       path, type(e).__name__, e)
        return False
