"""The executable store: a warm worker loads its step programs, compiled,
instead of tracing and lowering them again.

JAX's persistent compilation cache is keyed by the lowered module, so a program
has to be traced and lowered in full before the cache can be asked for it: with
every executable on the disk, two thirds of a warm set-up was still Python
(``PERF.md``, PR 51). This store lies beside that cache, in ``executables/``
under the directory :func:`dynamo_tpu.compile_cache.enable_compile_cache`
returns, and keeps each step program *serialised as compiled*
(``jax.experimental.serialize_executable``) under a key that is computed
without tracing anything. A process that has no persistent cache directory has
no store, and neither has a process of several hosts
(``jax.process_count() > 1``: its executables name devices of other processes;
it keeps the jitted functions' own path, ``store`` ``off`` in its spans).

**The key** is the design, because a stale hit is the only way the store can be
wrong: invalidating too much costs one cold start, too little serves a wrong
program. It has three parts.

1. The *build* (:func:`build_facts`, once a process; its digest names the
   directory the entries lie in): the bytes of every ``.py`` file of the
   ``dynamo_tpu`` package in path order, the versions of ``jax`` and
   ``jaxlib``, the backend's ``platform_version`` (it names the libtpu build),
   the device kind, the device and process counts, ``XLA_FLAGS`` and
   ``LIBTPU_INIT_ARGS``.
2. *Everything the runner was built from* (:func:`built_from`): the whole
   ``ModelConfig`` and every argument of ``ModelRunner.__init__`` but the
   weights, in a canonical form; a mesh by its axes and device ids. A runner
   given a function the key cannot see into (a ``forward_fn`` that is a closure
   or lives outside the package) has no store.
3. *The program* (:func:`program_key`): the jitted function's name, the
   dispatch site's name and ``dispatch_key``, the static keywords, and the tree
   structure with shape, dtype, weak type, layout and sharding of every dynamic
   argument. With it, read at each first sight and not once a process, what
   tracing can read besides (:func:`settings`): the ``jax.config`` values and
   the ``DYN*`` and ``JAX_*`` environment variables
   (``DYNAMO_PALLAS_INTERPRET``, ``DYN_DECODE_SPLITS``...).

What no key can see is a constant a step function closes over that is derived
from the *values* of the weights; the step programs have none (the benchmark's
``correct`` and ``tests/test_executable_store.py`` load executables written
under other weights), and a function patched in a running process: the test
suite runs without a store for that reason (``tests/conftest.py``).

**Files.** ``<root>/<build digest>/<key>.program``: a pickle of
``serialize(compiled)``'s three parts, compressed as JAX compresses its cache
(zstandard where installed, else zlib); ``BUILD.json`` beside them says what
the digest was made of. A write is a temporary file and a rename. The first
write under a new digest removes every other digest's directory but the one
used last, so two trees that take turns on one machine keep each other's
programs and the store holds two builds at most. Deleting the directory, or any
part of it, is always safe: a load that fails for whatever reason is a miss.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import json
import logging
import os
import pathlib
import pickle
import shutil
import tempfile
import time
import zlib
from typing import Any

logger = logging.getLogger(__name__)

DIRECTORY = "executables"
SUFFIX = ".program"
BUILD_FILE = "BUILD.json"
#: Environment variables a step program's tracing or lowering may read, by
#: prefix: the package's own (``DYN_*``, ``DYNAMO_*``) and JAX's flags.
ENV_PREFIXES = ("DYN", "JAX_")

HIT, MISS = "hit", "miss"  # a first call's ``store`` field; "off" where no store was asked
#: Platforms whose executables serialise whole after they were themselves loaded
#: from JAX's persistent cache. XLA's CPU backend drops kernels then (a loaded
#: entry fails when it runs: ``Function compare_reduce_fusion not found``), so
#: elsewhere a program that came out of JAX's cache is left to that cache.
RESERIALISES = ("tpu",)

# The persistent cache's directory (``enable_compile_cache`` sets it); None: no store.
_cache_dir: str | None = None


def set_cache_dir(path: str | None) -> None:
    """Where the persistent compile cache lies: the store lies under it.
    ``None`` (what a process has that never enabled the cache): no store."""
    global _cache_dir
    _cache_dir = path


def root() -> str | None:
    return None if _cache_dir is None else os.path.join(_cache_dir, DIRECTORY)


# -- the key ------------------------------------------------------------------------


def _sha(doc: Any) -> str:
    return hashlib.sha256(json.dumps(doc, sort_keys=True, separators=(",", ":")).encode()).hexdigest()


def package_digest(package_dir: str | os.PathLike) -> str:
    """The bytes of every ``.py`` file under ``package_dir``, in path order,
    each behind its relative path."""
    base = pathlib.Path(package_dir)
    h = hashlib.sha256()
    for path in sorted(base.rglob("*.py"), key=lambda p: p.relative_to(base).as_posix()):
        h.update(path.relative_to(base).as_posix().encode() + b"\0")
        h.update(path.read_bytes() + b"\0")
    return h.hexdigest()


@functools.cache
def build_facts() -> dict:
    """What a compiled program depends on that is the same for every program of
    this process; its digest (:func:`build_digest`) names the store's directory."""
    import jax
    import jaxlib

    device = jax.devices()[0]
    return {
        "package": package_digest(pathlib.Path(__file__).resolve().parent),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "platform": device.client.platform, "platform_version": device.client.platform_version,
        "device_kind": device.device_kind, "devices": jax.device_count(), "processes": jax.process_count(),
        "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""), "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
    }


def build_digest() -> str:
    return _sha(build_facts())[:32]


class Unkeyable(ValueError):
    """A value the key cannot hold in a form that says what a program would read of it."""


def canonical(value: Any) -> Any:
    """``value`` as JSON can hold it, the same for equal values in every process."""
    import jax
    import numpy as np

    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        return {"class": type(value).__qualname__,
                **{f.name: canonical(getattr(value, f.name)) for f in dataclasses.fields(value)}}
    if isinstance(value, dict):
        return {str(k): canonical(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [canonical(v) for v in value]
    if isinstance(value, (set, frozenset)):
        return sorted(canonical(v) for v in value)
    if isinstance(value, jax.sharding.Mesh):
        return {"axes": dict(value.shape), "devices": [d.id for d in value.devices.flat]}
    if isinstance(value, jax.Device):
        return {"device": value.id, "platform": value.platform}
    if isinstance(value, (np.dtype, type)):  # a dtype, or a scalar type that names one
        try:
            dtype = np.dtype(value)
        except TypeError:
            dtype = np.dtype(object)
        if dtype != object:
            return str(dtype)
    if callable(value) and hasattr(value, "__qualname__"):
        module, name = getattr(value, "__module__", "") or "", value.__qualname__
        if "<" in name or module.split(".")[0] != __name__.split(".")[0]:
            raise Unkeyable(f"{module}.{name}: a function the package's bytes do not hold, or a closure")
        return f"{module}.{name}"
    raise Unkeyable(f"{type(value).__qualname__}: no canonical form")


def built_from(arguments: dict) -> str:
    """The second part of the key: a runner's constructor arguments (its
    configuration among them), canonical. Raises :class:`Unkeyable`."""
    return json.dumps(canonical(arguments), sort_keys=True, separators=(",", ":"))


def _form(leaf: Any) -> list:
    """What a program specialises on of one dynamic argument."""
    import jax

    if isinstance(leaf, jax.Array):
        try:
            placed = str(leaf.format)  # the layout and the sharding (devices, memory kind)
        except Exception:  # a backend that reports no layout
            placed = str(leaf.sharding)
        return [list(leaf.shape), str(leaf.dtype), bool(leaf.aval.weak_type), placed]
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return [list(leaf.shape), str(leaf.dtype), False, type(leaf).__qualname__]
    return [type(leaf).__qualname__]  # a Python scalar: weakly typed by its kind


@functools.cache
def _config_names() -> tuple[str, ...]:
    """The ``jax.config`` names the key reads, fixed when the process opens its
    store (before any step program is traced). JAX defines some flags only when
    a trace first imports their module (``jax_pallas_*``, ``jax_mosaic_*``): a
    cold run would come to know names that a warm run, which traces nothing,
    never learns, and the two would disagree on every key. A flag defined that
    late is at its default unless the ``JAX_*`` environment, which the key
    holds, says otherwise. Where a cache lies and how it is kept is no part of
    a program: those names are left out, as they are from JAX's own key."""
    import jax

    return tuple(sorted(k for k in jax.config.values if "cache" not in k))


def settings() -> dict:
    """What tracing and lowering can read that is no argument of anything: the
    ``jax.config`` values, the ``DYN*`` and ``JAX_*`` environment."""
    import jax

    values = jax.config.values
    return {"config": {k: repr(values.get(k)) for k in _config_names()},
            "env": {k: v for k, v in os.environ.items() if k.startswith(ENV_PREFIXES) and "CACHE" not in k}}


def program_key(runner: str, fn_name: str, program: str, dispatch_key: tuple, statics: tuple,
                args: tuple, kwargs: dict) -> str:
    """The entry's name: the runner's part, the program's, and the settings."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
    return _sha({"runner": runner, "settings": settings(), "fn": fn_name, "program": program,
                 "dispatch_key": [repr(k) for k in dispatch_key], "statics": [[k, repr(v)] for k, v in statics],
                 "tree": str(tree), "leaves": [_form(leaf) for leaf in leaves]})


# -- the files ----------------------------------------------------------------------


def _compress(data: bytes) -> bytes:
    try:
        import zstandard
    except ImportError:
        return zlib.compress(data)
    return zstandard.ZstdCompressor().compress(data)


def _decompress(data: bytes) -> bytes:
    try:
        import zstandard
    except ImportError:
        return zlib.decompress(data)
    return zstandard.ZstdDecompressor().decompress(data)


class ExecutableStore:
    """The entries of one build under ``root``. Neither :meth:`load` nor
    :meth:`save` raises: bring-up never fails on the store."""

    def __init__(self, root: str, digest: str, facts: dict | None = None) -> None:
        self.root = root
        self.digest = digest
        self.facts = facts
        self.dir = os.path.join(root, digest)
        self.hits = self.misses = self.failed_loads = self.written = self.failed_writes = 0
        self.read_s = self.write_s = 0.0
        self.bytes_read = self.bytes_written = 0
        self._claimed = False
        try:  # used now: a newer build's first write keeps this one
            os.utime(self.dir)
        except OSError:
            pass

    def path(self, key: str) -> str:
        return os.path.join(self.dir, key + SUFFIX)

    def load(self, key: str, devices: list):
        """The compiled program under ``key``, loaded onto ``devices`` (the
        runner's one device, or its mesh's in order), or ``None``: no such
        entry, or one that cannot be read (truncated, written by a JAX that
        serialises otherwise, compressed by a codec this process lacks)."""
        from jax.experimental.serialize_executable import deserialize_and_load

        t0 = time.perf_counter()
        try:
            with open(self.path(key), "rb") as f:
                data = f.read()
        except OSError:
            self.misses += 1
            return None
        try:
            payload, in_tree, out_tree = pickle.loads(_decompress(data))
            compiled = deserialize_and_load(payload, in_tree, out_tree, backend=devices[0].client,
                                            execution_devices=devices)
        except Exception as e:  # anything at all: the miss path makes the program and writes it again
            logger.warning("executable store: entry %s does not load (%s: %s)", key[:16], type(e).__name__, e)
            self.failed_loads += 1
            self.misses += 1
            return None
        finally:
            self.read_s += time.perf_counter() - t0
        self.hits += 1
        self.bytes_read += len(data)
        return compiled

    def unhit(self) -> None:
        """The program :meth:`load` last returned was refused by its first call: a miss after all."""
        self.hits -= 1
        self.failed_loads += 1
        self.misses += 1

    def save(self, key: str, compiled) -> bool:
        """Writes ``compiled`` under ``key``: a temporary file in the entry's
        directory, then a rename, so a reader sees a whole entry or none."""
        from jax.experimental.serialize_executable import serialize

        t0 = time.perf_counter()
        tmp = None
        try:
            data = _compress(pickle.dumps(serialize(compiled), protocol=pickle.HIGHEST_PROTOCOL))
            self._claim()
            fd, tmp = tempfile.mkstemp(dir=self.dir, prefix=".writing-")
            with os.fdopen(fd, "wb") as f:
                f.write(data)
            os.replace(tmp, self.path(key))
        except Exception as e:  # a full disk, a read-only directory, an executable that does not serialise
            logger.warning("executable store: entry %s not written (%s: %s)", key[:16], type(e).__name__, e)
            self.failed_writes += 1
            if tmp is not None:
                with contextlib.suppress(OSError):
                    os.unlink(tmp)
            return False
        finally:
            self.write_s += time.perf_counter() - t0
        self.written += 1
        self.bytes_written += len(data)
        return True

    def _claim(self) -> None:
        """This process's first write. Under a digest that has no directory
        yet, every other digest's directory goes but the one used last."""
        if self._claimed:
            return
        self._claimed = True
        if os.path.isdir(self.dir):
            return
        others = [e.path for e in os.scandir(self.root) if e.is_dir()] if os.path.isdir(self.root) else []
        os.makedirs(self.dir, exist_ok=True)
        for path in sorted(others, key=os.path.getmtime)[:-1]:
            shutil.rmtree(path, ignore_errors=True)
        if self.facts is not None:
            with open(os.path.join(self.dir, BUILD_FILE), "w") as f:
                json.dump(self.facts, f, indent=1, sort_keys=True)

    def counters(self) -> dict:
        """This process's traffic with the store."""
        return {"digest": self.digest, "hits": self.hits, "misses": self.misses, "failed_loads": self.failed_loads,
                "written": self.written, "failed_writes": self.failed_writes,
                "read_s": round(self.read_s, 3), "write_s": round(self.write_s, 3),
                "bytes_read": self.bytes_read, "bytes_written": self.bytes_written}


def describe(root_dir: str | None = None) -> dict:
    """What lies in the store on the disk: each build's directory with its
    entries, bytes and age (``tools/compile_cache_probe.py``)."""
    root_dir = root_dir or root()
    builds = []
    if root_dir and os.path.isdir(root_dir):
        for entry in sorted(os.scandir(root_dir), key=lambda e: e.name):
            if entry.is_dir():
                files = [f for f in os.scandir(entry.path) if f.name.endswith(SUFFIX)]
                builds.append({"digest": entry.name, "entries": len(files),
                               "bytes": sum(f.stat().st_size for f in files),
                               "used_s_ago": round(time.time() - entry.stat().st_mtime, 1)})
    return {"root": root_dir, "builds": builds}


def open_store() -> ExecutableStore | None:
    """This process's store, or ``None``: no persistent cache directory, or a
    process of several hosts."""
    import jax

    where = root()
    if where is None or jax.process_count() > 1:
        return None
    _config_names()
    return ExecutableStore(where, build_digest(), build_facts())


# -- one runner's programs ------------------------------------------------------------


class StepPrograms:
    """One runner's step programs as it calls them: each compiled program kept
    under the jitted function's name, the dispatch site's name, the
    ``dispatch_key`` and the static keywords. The first sight of such a key
    looks in the store (found: loaded and called; not found: traced, lowered
    and compiled as a jitted function's first call would, kept, written), every
    later one calls the kept program. The arguments' forms are read once, at
    the first sight; a kept program refuses arguments of another form, where a
    jitted function would silently compile again: such a dispatch goes to the
    jitted function, and ``on_refusal`` hears of it, because it means that a
    ``dispatch_key`` does not hold everything its program specialises on."""

    def __init__(self, store: ExecutableStore, runner_key: str, devices: list, *, note=None, on_refusal=None,
                 cache_hits=None) -> None:
        self.store = store
        self.runner_key = runner_key
        self.devices = devices
        self._note = note or (lambda status, read_s: None)
        self._on_refusal = on_refusal or (lambda program, dispatch_key, error: None)
        # Programs JAX's persistent cache has handed this thread (observability/compile.py counts them).
        self._cache_hits = cache_hits or (lambda: 0)
        self._kept: dict[tuple, Any] = {}
        self.left_to_cache = 0  # compiled programs not written: out of JAX's cache, on a platform not in RESERIALISES

    def call(self, fn, program: str, dispatch_key: tuple, static_names: tuple, args: tuple, kwargs: dict):
        statics = tuple((k, kwargs[k]) for k in static_names if k in kwargs)
        dynamic = {k: v for k, v in kwargs.items() if k not in static_names}
        key = (fn.__name__, program, dispatch_key, statics)
        compiled = self._kept.get(key)
        if compiled is None:
            return self._first(fn, key, args, kwargs, dynamic)
        try:
            return compiled(*args, **dynamic)
        except (TypeError, ValueError) as e:  # the arguments' check, before anything ran or was donated
            self._on_refusal(program, dispatch_key, e)
            return fn(*args, **kwargs)

    def _first(self, fn, key: tuple, args: tuple, kwargs: dict, dynamic: dict):
        try:
            name = program_key(self.runner_key, *key, args, dynamic)
        except Exception:  # an argument no form can be read of: bring-up does not fail on the store
            logger.exception("executable store: no key for %s %s; the jitted function takes the call", key[0], key[2])
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        compiled = self.store.load(name, self.devices)
        read_s = time.perf_counter() - t0
        if compiled is not None:
            try:
                out = compiled(*args, **dynamic)
            except (TypeError, ValueError) as e:  # an entry whose tree or forms are not this call's
                logger.warning("executable store: entry %s refuses its call (%s)", name[:16], e)
                self.store.unhit()
            else:
                self._kept[key] = compiled
                self._note(HIT, read_s)
                return out
        hits = self._cache_hits()
        compiled = fn.lower(*args, **kwargs).compile()
        out = compiled(*args, **dynamic)
        self._kept[key] = compiled
        if self._cache_hits() == hits or self.devices[0].platform in RESERIALISES:
            self.store.save(name, compiled)  # behind the enqueue: the device runs the step meanwhile
        else:
            self.left_to_cache += 1
        self._note(MISS, read_s)
        return out
