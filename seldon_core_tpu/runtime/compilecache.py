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

Three things live in that directory.  JAX's own entries, keyed by the hash
of a LOWERED module: whoever wants one has to trace the program first.  The
program record (``genserver-programs-<deployment>.json``): which shapes of
the scheduler's two paged programs a deployment dispatched, so that the
next boot brings exactly those up before its first request.  And the
program store (``ProgramStore``, ``genserver-program-*.pkl``): those
shapes' executables themselves, serialised, under a key that needs NO
trace -- the deployment, the shape, the static arguments, every byte of
this package, the toolchain, the device and the environment -- which a warm
boot deserialises straight into the table the scheduler dispatches from
(runtime/genserver.py ``_load``, ``_program``).  The files are pickles:
read them only from a directory that is trusted with executables anyway.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import hashlib
import json
import logging
import os
import pickle
from typing import Any, Dict, Mapping, Optional, Sequence, Set

__all__ = [
    "ProgramStore",
    "compile_cache_dir",
    "enable_compile_cache",
    "package_digest",
    "program_record_path",
    "read_program_record",
    "trace_environment",
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
# ``_load_programs``).  A HINT that holds no executable and decides nothing
# about whether code is current -- the program store's key and JAX's own
# do -- so a record that is stale, from other source, corrupt or
# half-written costs at worst a compile at boot and never a wrong program.
# ---------------------------------------------------------------------------

_RECORD_VERSION = 1
#: program kind -> entries of one shape: (rows, chunk, blocks) / (rows, blocks)
_RECORD_ARITY = {"prefill": 3, "decode": 2}


def _write_whole(path: str, data: bytes) -> None:
    """``data`` into ``path`` atomically: a reader sees the old file or the
    new one, and a write that fails leaves nothing behind."""
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except OSError:
        with contextlib.suppress(OSError):
            os.remove(tmp)
        raise


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
    try:
        _write_whole(path, json.dumps(doc).encode())
        return True
    except OSError as e:
        logger.warning("program record %s not written (%s: %s)",
                       path, type(e).__name__, e)
        return False


# ---------------------------------------------------------------------------
# The program store: the executables of the shapes the record lists, kept
# beside it so that a warm boot neither traces nor lowers anything.  JAX's
# persistent cache cannot give that: its key is the hash of the lowered
# module, and lowering a 30-layer paged program is 0.3-2 s of Python, 10-24
# times a boot on ONE thread (PERF.md section 5 (1)).  The key here is made
# of what can be read without a trace, and is therefore coarser: ANY edit
# to ANY file of this package is another key, so a change to the source can
# never run the executable its parent compiled (tests/test_program_store.py
# holds that).  What the key cannot see -- a JAX patched in place under one
# version string, a ``jax.config`` value set in code outside this package
# (of JAX's configuration the key holds the ``JAX_*`` environment only) --
# it leaves to the operator, who clears the directory as for JAX's own cache.
# ---------------------------------------------------------------------------

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: environment names that can change what a program traces or compiles to
_TRACE_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
_TRACE_ENV_PREFIXES = ("SELDON_TPU_", "JAX_")


@functools.lru_cache(maxsize=None)
def package_digest(root: str = _PACKAGE_DIR) -> str:
    """A digest of every file under ``root`` -- the installed package:
    each one's path below it and its bytes, whatever module it is and
    whether or not a program imports it.  Left out is only what the
    interpreter derives from those files and rewrites as it runs
    (``__pycache__``, ``*.pyc``).  Computed once a process and root (a few
    milliseconds for the package's ~100 files)."""
    h = hashlib.sha256()
    for folder, dirs, files in os.walk(root):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            if name.endswith(".pyc"):
                continue
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                body = f.read()
            h.update(f"{os.path.relpath(path, root)}\0{len(body)}\0".encode())
            h.update(body)
    return h.hexdigest()[:16]


# taken NOW, as this module -- and with it the package -- is loaded, not when
# a store is built some twenty seconds into the boot: files replaced in place
# meanwhile must not lend their digest to executables of the code that runs
package_digest()


def trace_environment(environ: Mapping[str, str] = os.environ
                      ) -> Dict[str, str]:
    """The environment as far as it can change a trace or a compile:
    ``XLA_FLAGS``, ``LIBTPU_INIT_ARGS`` and every ``SELDON_TPU_*`` and
    ``JAX_*`` value.  Left out are names that say only WHERE files go or
    how many may stay -- ``*_DIR`` (the compile cache's own, a profile
    window's, an audit log's: a harness that gives every run a directory
    of its own must still boot warm) and the cache's
    ``JAX_COMPILATION_CACHE_MAX_SIZE``."""
    return {
        name: value for name, value in sorted(environ.items())
        if (name in _TRACE_ENV or name.startswith(_TRACE_ENV_PREFIXES))
        and not name.endswith("_DIR")
        and name != "JAX_COMPILATION_CACHE_MAX_SIZE"}


def _toolchain(devices: Sequence[Any]) -> Dict[str, Any]:
    """What compiled a program and what it runs on: the versions of jax and
    jaxlib, the backend's own (libtpu's build on a TPU), the kind of device,
    how many the process sees and which of them run the program."""
    import jax
    import jaxlib

    client = devices[0].client
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "platform": client.platform,
            "platform_version": client.platform_version,
            "device_kind": devices[0].device_kind,
            "device_count": jax.device_count(),
            "devices": [d.id for d in devices]}


class ProgramStore:
    """The stored executables of ONE deployment (``identity``, as the record
    has it) in ``directory``, on ``devices``.  A file is
    ``genserver-program-<identity>-<package>-<key>.pkl``: the pickled triple
    of ``jax.experimental.serialize_executable.serialize``.  ``toolchain``,
    ``package`` and ``environ`` are what a boot reads of itself; a test
    hands in others."""

    def __init__(self, directory: str, identity: str, devices: Sequence[Any],
                 package: Optional[str] = None,
                 toolchain: Optional[Dict[str, Any]] = None,
                 environ: Mapping[str, str] = os.environ):
        self._devices = list(devices)
        self._package = package or package_digest()
        self._facts = json.dumps({
            "identity": identity, "package": self._package,
            "toolchain": toolchain or _toolchain(self._devices),
            "environment": trace_environment(environ)}, sort_keys=True)
        self._identity_prefix = os.path.join(
            directory, "genserver-program-"
            + hashlib.sha256(identity.encode()).hexdigest()[:16] + "-")
        self._prefix = self._identity_prefix + self._package + "-"
        #: whether this backend gives back whole an executable that it
        #: LOADED from a file (JAX's persistent cache's hit, or this store's).
        #: Observed, not assumed: the TPU runtime does (PERF.md section 6,
        #: PR 53: a v5e boot served from executables serialised after such a
        #: load); XLA:CPU's comes back without its kernels' code and fails
        #: when it first runs.  A backend nobody has tried counts as the
        #: latter.
        self.reserialises = self._devices[0].client.platform == "tpu"
        self._writable = True       # until a write or a removal fails
        self._refused = False       # an executable did not serialise
        self._swept = False         # other digests' files were removed

    def path(self, kind: str, shape: tuple, statics: Mapping[str, Any]) -> str:
        """Where the executable of ``(kind, shape)`` lives, ``statics`` being
        the static arguments as the scheduler states them.  Nothing is
        traced: the name is a digest of these and of what ``__init__``
        read."""
        key = hashlib.sha256(repr((
            self._facts, kind, tuple(shape),
            sorted((k, repr(v)) for k, v in statics.items()))).encode())
        return self._prefix + key.hexdigest()[:24] + ".pkl"

    def files(self) -> list:
        """This deployment's files, of whatever package digest."""
        return sorted(glob.glob(glob.escape(self._identity_prefix) + "*"))

    def load(self, path: str):
        """The ``jax.stages.Compiled`` stored at ``path``, loaded onto the
        devices; None where there is no such file (in silence) or where it
        does not load -- truncated, not a pickle, written by another
        backend build: one warning, and the file is removed so that the
        program compiled in its place is stored anew."""
        from jax.experimental.serialize_executable import deserialize_and_load

        try:
            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            return deserialize_and_load(
                payload, in_tree, out_tree, backend=self._devices[0].client,
                execution_devices=self._devices)
        except FileNotFoundError:
            return None
        except Exception as e:  # noqa: BLE001 - any damage: the traced path
            logger.warning("stored program %s ignored and removed (%s: %s): "
                           "traced and compiled instead",
                           path, type(e).__name__, str(e)[:200])
            with contextlib.suppress(OSError):
                os.remove(path)
            return None

    def sweep(self) -> None:
        """Remove this deployment's files of OTHER package digests, once a
        process: no boot of this package can load them, and the directory
        holds one copy a deployment, not one a source change.  A boot does
        it, and the first write.  A file that cannot be removed ends the
        storing as a failed write does."""
        if self._swept:
            return
        self._swept = True
        try:
            for other in self.files():
                if not other.startswith(self._prefix):
                    os.remove(other)
        except OSError as e:
            self._writable = False
            logger.warning(
                "stored programs of another package not removed (%s: %s): "
                "this process stores nothing", type(e).__name__, e)

    def save(self, path: str, compiled) -> bool:
        """Serialise ``compiled`` into ``path``, atomically (a reader sees
        the whole file or none), other digests' files swept first.  False
        where the executable does not serialise (XLA:CPU's sort comparators
        do not: that program is traced by every boot; the first such says
        so) and where the directory cannot be written: one warning, and the
        storing ends for this process, as the recording does."""
        from jax.experimental.serialize_executable import serialize

        self.sweep()
        if not self._writable:
            return False
        try:
            blob = pickle.dumps(serialize(compiled),
                                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:  # noqa: BLE001 - whatever a backend raises
            logger.log(
                logging.DEBUG if self._refused else logging.WARNING,
                "program %s does not serialise (%s: %s): every boot traces "
                "it", path, type(e).__name__, str(e)[:200])
            self._refused = True
            return False
        try:
            _write_whole(path, blob)
            return True
        except OSError as e:
            self._writable = False
            logger.warning(
                "program %s not stored (%s: %s): this process stores no more, "
                "the next boot traces what it lacks",
                path, type(e).__name__, e)
            return False
