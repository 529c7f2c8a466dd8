"""Executor: AOT compile cache keyed by (program, shapes), shape bucketing.

The TPU-first design constraint this enforces (SURVEY.md §7 hard parts):
everything under jit is traced once and compiled; dynamic request shapes must
be bucketed to a small, fixed set so XLA compiles a bounded number of
programs. The cache is the analog of the reference keeping its expensive init
(DB connect) in the container, not per request (gofr.go:63-97).
"""

from __future__ import annotations

import functools
import hashlib
import os
import pickle
import threading
import time
import zlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)

# -- where compiled programs persist: ONE rule ---------------------------------
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout (git-ignored): the path is part of JAX's
# cache key, so a directory named after a pid, a time or a temp name would
# never hit twice
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".compile_cache")


def compile_cache_dir(override: Optional[str] = None) -> str:
    """The directory both compile caches live in — JAX's persistent
    compilation cache and the Executor's serialized executables (.jexec).

    Where JAX_COMPILATION_CACHE_DIR is set (the machine's owner placed the
    cache) it wins and nothing here sets another; else an explicit
    `override` (a deployment's PROGRAM_CACHE_DIR, shared by its replicas);
    else DEFAULT_COMPILE_CACHE_DIR."""
    return (os.environ.get(COMPILE_CACHE_ENV) or override
            or DEFAULT_COMPILE_CACHE_DIR)


def enable_compile_cache(override: Optional[str] = None) -> str:
    """Turn JAX's persistent cache on at compile_cache_dir(override) and
    return the directory for Executor(cache_dir=...). Entry points call
    this before their first jit, so that weight init, the device probe and
    the scoring families land in the same cache as the executor's
    programs. Programs that compile in under a second are kept too: a
    boot traces dozens of them.

    On the CPU backend only the Executor's artifacts persist, unless the
    environment placed JAX's cache itself: XLA:CPU executables that
    jaxlib 0.9.0 reloads from JAX's cache fail when run beside others in
    one process ("NOT_FOUND: Function add_convert_fusion not found")."""
    import jax

    path = compile_cache_dir(override)
    os.makedirs(path, exist_ok=True)
    if os.environ.get(COMPILE_CACHE_ENV):
        return path                             # JAX reads it itself
    if jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@functools.lru_cache(maxsize=None)
def _package_digest() -> str:
    """Hash of this package's sources. A persisted executable is looked up
    by the program's NAME and the code object of its outermost function;
    the model and kernel code that function calls is not in that key, so
    every artifact also carries this digest and an edit anywhere in the
    package retires them all."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digest = hashlib.sha256()
    for folder, _, files in sorted(os.walk(root)):
        for fname in sorted(files):
            if fname.endswith(".py"):
                with open(os.path.join(folder, fname), "rb") as fp:
                    digest.update(fp.read())
    return digest.hexdigest()


def next_bucket(n: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    """Smallest bucket >= n. Raises if n exceeds the largest bucket."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"size {n} exceeds largest bucket {buckets[-1]}")


def pad_to(array, size: int, axis: int = 0, value=0):
    """Pad `array` along `axis` up to `size` with `value` (no-op if already there)."""
    import jax.numpy as jnp
    import numpy as np

    xp = jnp if not isinstance(array, np.ndarray) else np
    current = array.shape[axis]
    if current == size:
        return array
    if current > size:
        raise ValueError(f"array dim {current} larger than target {size}")
    widths = [(0, 0)] * array.ndim
    widths[axis] = (0, size - current)
    return xp.pad(array, widths, constant_values=value)


def _code_digest(code, digest=None) -> str:
    """Hash of a code object: its bytecode, names and constants, nested
    code objects included (`x+1` and `x+2` share co_code; the constant
    lives in co_consts). Not marshal.dumps: marshal writes a back-reference
    for any constant whose REFCOUNT is above one, so its bytes change once
    the function has been traced, and the first program of every family
    was saved under a name the next boot never looked for."""
    digest = digest if digest is not None else hashlib.sha256()
    digest.update(code.co_code)
    digest.update(repr((code.co_names, code.co_varnames, code.co_freevars,
                        code.co_cellvars, code.co_argcount,
                        code.co_kwonlyargcount, code.co_flags)).encode())
    for const in code.co_consts:
        if hasattr(const, "co_code"):
            _code_digest(const, digest)
        elif isinstance(const, frozenset):   # order follows the hash seed
            digest.update(repr(sorted(map(repr, const))).encode())
        else:
            digest.update(repr(const).encode())
    return digest.hexdigest()


def _abstract_key(tree) -> Tuple:
    """Hashable (shape, dtype) signature of an argument pytree."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    sig = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sig.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            sig.append((type(leaf).__name__, repr(leaf)))
    return tuple(sig)


class CompiledProgram:
    def __init__(self, compiled, name: str, key: Tuple):
        self.compiled = compiled
        self.name = name
        self.key = key
        self.executions = 0
        # compile-table bookkeeping (Executor.compile_table): how this
        # program came to exist, what it cost, how often the cache served it
        self.compile_seconds = 0.0
        self.hits = 0
        self.source = "compiled"        # "compiled" | "disk"

    def __call__(self, *args):
        self.executions += 1
        return self.compiled(*args)


class Executor:
    """Compile-once execute-many wrapper around jax.jit with an explicit cache.

    compile(name, fn, args, ...) AOT-lowers + compiles for the exact arg
    shapes; subsequent calls with the same shapes hit the cache. `run` is the
    one-call convenience: bucket -> compile-or-hit -> execute.
    """

    def __init__(self, tpu_client=None, logger=None, metrics=None,
                 cache_dir: Optional[str] = None):
        self.tpu = tpu_client
        self.logger = logger if logger is not None else getattr(tpu_client, "logger", None)
        self.metrics = metrics if metrics is not None else getattr(tpu_client, "metrics", None)
        self._cache: Dict[Tuple, CompiledProgram] = {}
        self._lock = threading.Lock()
        # fault-injection plane (tpu/faults.py): None in production; armed
        # deployments can add latency to (or fail) compile lookups
        self.faults = None
        # step-ledger attribution (tpu/stepledger.py): called with
        # (name, seconds) after every cache-MISS compile so the engine can
        # re-attribute compile time out of the segment it happened under.
        # One callback per executor — an executor shared across engines
        # reports to whichever engine bound it last (attribution only;
        # correctness never depends on it)
        self.on_compile = None
        # compiled-program persistence (SURVEY §2.5 item 2): serialized PJRT
        # executables keyed by (program, shapes, backend); a second boot
        # loads them instead of re-tracing + re-compiling
        self.cache_dir = cache_dir
        self.disk_hits = 0
        if cache_dir:
            os.makedirs(cache_dir, exist_ok=True)
            self._prune_stale_artifacts()

    # artifacts whose fingerprint can no longer be produced (code changed,
    # topology changed, fingerprint schema evolved) are never matched and
    # never hit the failed-load cleanup — age them out so the cache dir
    # stays bounded. Loads touch mtime, so live artifacts survive.
    PRUNE_AGE_S = 30 * 86400

    def _prune_stale_artifacts(self) -> None:
        now = time.time()  # lint: clock-ok compared against file mtimes, which are wall-clock
        try:
            for fname in os.listdir(self.cache_dir):
                if fname.endswith(".jexec"):
                    cutoff = now - self.PRUNE_AGE_S
                elif ".jexec.tmp." in fname:
                    # crash-during-persist leftovers (the atomic-replace
                    # staging files); an hour covers any live writer
                    cutoff = now - 3600
                else:
                    continue
                path = os.path.join(self.cache_dir, fname)
                try:
                    if os.path.getmtime(path) < cutoff:
                        os.remove(path)
                except OSError:
                    pass
        except OSError:
            pass

    def _observe_compile(self, name: str, seconds: float, hit: bool) -> None:
        if self.metrics is not None:
            try:
                if hit:
                    self.metrics.increment_counter("app_tpu_compile_cache_hits")
                else:
                    self.metrics.increment_counter("app_tpu_compile_total")
            except Exception:  # noqa: BLE001 - metrics may not be registered in tests
                pass
        if not hit and self.logger is not None:
            self.logger.infof("compiled %s in %.2fs", name, seconds)

    @staticmethod
    def _args_device_sig(args) -> Tuple:
        """ORDERED device ids the example args are committed to — part of
        the disk fingerprint so a tp=8 artifact can never be resurrected
        by a single-device engine with identical shapes, and a mesh over
        the same devices in a DIFFERENT order gets its own artifact (the
        restore pins the recorded order; an order mismatch would fail on
        every call with no recompile fallback)."""
        import jax

        ids = set()
        for leaf in jax.tree_util.tree_leaves(args):
            sharding = getattr(leaf, "sharding", None)
            if sharding is None:
                continue
            assignment = getattr(sharding, "_device_assignment", None)
            if assignment and len(assignment) > 1:
                return tuple(d.id for d in assignment)
            mesh = getattr(sharding, "mesh", None)
            devices = getattr(mesh, "devices", None)
            if devices is not None and getattr(devices, "size", 1) > 1:
                return tuple(d.id for d in devices.flat)
            device_set = getattr(sharding, "device_set", None)
            if device_set:
                ids |= {d.id for d in device_set}
        return tuple(sorted(ids))   # single-device / uncommitted args

    def _disk_path(self, key: Tuple, fn: Callable,
                   dev_sig: Tuple = ()) -> Optional[str]:
        if not self.cache_dir:
            return None
        import jax

        try:
            device = jax.devices()[0]
            # the whole code object (_code_digest) AND the closure cell
            # values go into the fingerprint: engine program factories
            # close over the model config, and neither a changed constant
            # nor a changed config may resurrect a stale executable.
            # Address-bearing reprs (plain objects) are reduced to their
            # type name so the digest is stable across processes.
            import re

            code = getattr(fn, "__code__", None)
            cells = []
            for cell in (getattr(fn, "__closure__", None) or ()):
                try:
                    text = repr(cell.cell_contents)
                except Exception:  # noqa: BLE001
                    text = "?"
                if " at 0x" in text:
                    text = type(cell.cell_contents).__name__
                cells.append(re.sub(r"0x[0-9a-f]+", "", text))
            fingerprint = (key, jax.__version__, device.platform,
                           device.device_kind,
                           device.client.platform_version, dev_sig,
                           _code_digest(code) if code is not None else "",
                           tuple(cells), _package_digest())
        except Exception:  # noqa: BLE001
            return None
        digest = hashlib.sha256(repr(fingerprint).encode()).hexdigest()[:32]
        return os.path.join(self.cache_dir, f"{digest}.jexec")

    def _load_from_disk(self, name: str, key: Tuple, fn: Callable,
                        dev_sig: Tuple = ()) -> Optional[CompiledProgram]:
        path = self._disk_path(key, fn, dev_sig)
        if path is None or not os.path.exists(path):
            return None
        import jax
        from jax.experimental import serialize_executable

        try:
            with open(path, "rb") as fp:
                blob, in_tree, out_tree, device_ids = pickle.loads(
                    zlib.decompress(fp.read()))
            # the artifact records the mesh's DEVICE ORDER (a device count
            # cannot reconstruct an assignment; a wrong order would
            # silently mis-shard). Restore exactly that ordering — if any
            # recorded device is gone, the topology changed: discard
            by_id = {d.id: d for d in jax.devices()}
            if device_ids and not all(i in by_id for i in device_ids):
                raise ValueError(f"device ids {device_ids} not all present")
            execution_devices = ([by_id[i] for i in device_ids]
                                 if device_ids else jax.devices()[:1])
            compiled = serialize_executable.deserialize_and_load(
                blob, in_tree, out_tree, execution_devices=execution_devices)
        except Exception as exc:  # noqa: BLE001 - stale/foreign artifact
            if self.logger is not None:
                self.logger.warnf("discarding persisted program %s: %s",
                                  path, exc)
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        if self.metrics is not None:
            try:
                self.metrics.increment_counter("app_tpu_compile_disk_hits")
            except Exception:  # noqa: BLE001
                pass
        self.disk_hits += 1
        try:
            os.utime(path)   # keep hot artifacts out of the age-out prune
        except OSError:
            pass
        if self.logger is not None:
            self.logger.infof("loaded %s from program cache", name)
        program = CompiledProgram(compiled, name, key)
        program.source = "disk"
        return program

    @staticmethod
    def _device_order(compiled):
        """The compiled executable's ordered device assignment, or None if
        it cannot be determined (then multi-device persist is skipped)."""
        import jax

        for s in jax.tree_util.tree_leaves(compiled.input_shardings):
            assignment = getattr(s, "_device_assignment", None)
            if assignment:
                return list(assignment)
            mesh = getattr(s, "mesh", None)
            if mesh is not None:
                try:
                    return list(mesh.devices.flat)
                except Exception:  # noqa: BLE001
                    pass
        return None

    def _save_to_disk(self, key: Tuple, fn: Callable, compiled,
                      dev_sig: Tuple = ()) -> None:
        path = self._disk_path(key, fn, dev_sig)
        if path is None:
            return
        import jax
        from jax.experimental import serialize_executable

        try:
            devices = set()
            for s in jax.tree_util.tree_leaves(compiled.input_shardings):
                devices |= getattr(s, "device_set", set())
            if len(devices) > 1:
                # multi-device (mesh) program: persist the mesh's device
                # ORDERING alongside the blob so a later boot restores the
                # exact assignment (VERDICT r3 weak #5 — TP programs used
                # to recompile every restart). Order unknown -> skip
                order = self._device_order(compiled)
                if order is None or len(order) != len(devices):
                    return
                device_ids = [d.id for d in order]
            elif devices:
                # single-device too: a program committed to device 3 must
                # not reload pinned to device 0 (it would fail on every
                # call with a device mismatch, with no recompile fallback)
                device_ids = [next(iter(devices)).id]
            else:
                device_ids = []   # uncommitted: default device at load
            blob, in_tree, out_tree = serialize_executable.serialize(compiled)
            # level 1: an executable is mostly tables and shrinks severalfold
            # at once; the cache directory holds JAX's copy of each too
            payload = zlib.compress(
                pickle.dumps((blob, in_tree, out_tree, device_ids)), 1)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as fp:
                fp.write(payload)
            os.replace(tmp, path)
        except Exception as exc:  # noqa: BLE001 - persistence is best-effort
            if self.logger is not None:
                self.logger.debugf("could not persist program: %s", exc)

    def compile(self, name: str, fn: Callable, args: Tuple,
                static_argnums: Tuple[int, ...] = (),
                donate_argnums: Tuple[int, ...] = (),
                in_shardings=None, out_shardings=None) -> CompiledProgram:
        import jax

        import re as _re

        if self.faults is not None:  # chaos drills: slow/failed compiles
            self.faults.hit("executor.compile", name=name)

        shard_sig = ""
        if in_shardings is not None or out_shardings is not None:
            # explicit shardings change the compiled program for identical
            # arg shapes; scrub addresses so the signature is stable
            shard_sig = _re.sub(r"0x[0-9a-f]+", "",
                                repr((in_shardings, out_shardings)))
        # the device signature is part of the IN-MEMORY key too: two engines
        # sharing one Executor with identical names/shapes but different
        # meshes (or devices) must not be handed each other's programs
        # (ADVICE r4) — the same topology identity that keys disk artifacts
        dev_sig = self._args_device_sig(args)
        key = (name, _abstract_key([a for i, a in enumerate(args) if i not in static_argnums]),
               tuple(static_argnums), tuple(donate_argnums), shard_sig,
               dev_sig)
        with self._lock:
            cached = self._cache.get(key)
        if cached is not None:
            cached.hits += 1
            self._observe_compile(name, 0.0, hit=True)
            return cached

        loaded = self._load_from_disk(name, key, fn, dev_sig)
        if loaded is not None:
            with self._lock:
                loaded = self._cache.setdefault(key, loaded)
            return loaded

        start = time.monotonic()
        kwargs: Dict[str, Any] = {}
        if static_argnums:
            kwargs["static_argnums"] = static_argnums
        if donate_argnums:
            kwargs["donate_argnums"] = donate_argnums
        if in_shardings is not None:
            kwargs["in_shardings"] = in_shardings
        if out_shardings is not None:
            kwargs["out_shardings"] = out_shardings
        jitted = jax.jit(fn, **kwargs)
        compiled = jitted.lower(*args).compile()
        program = CompiledProgram(compiled, name, key)
        elapsed = time.monotonic() - start
        program.compile_seconds = elapsed
        self._save_to_disk(key, fn, compiled, dev_sig)
        with self._lock:
            # a racing thread may have compiled the same key; keep the first
            program = self._cache.setdefault(key, program)
        self._observe_compile(name, elapsed, hit=False)
        if self.on_compile is not None:
            try:
                self.on_compile(name, elapsed)
            except Exception:  # noqa: BLE001 - attribution is best-effort
                pass
        return program

    def run(self, name: str, fn: Callable, *args, **compile_kwargs):
        program = self.compile(name, fn, args, **compile_kwargs)
        start = time.monotonic()
        out = program(*args)
        if self.metrics is not None:
            try:
                self.metrics.increment_counter("app_tpu_execute_total")
                self.metrics.record_histogram("app_tpu_execute_seconds", time.monotonic() - start)
            except Exception:  # noqa: BLE001
                pass
        return out

    def warmup(self, name: str, fn: Callable, example_args: Tuple, **kw) -> None:
        """Pre-compile at boot so the first request doesn't pay compile latency
        (the expensive-init-in-container rule, SURVEY.md §3.1)."""
        self.compile(name, fn, example_args, **kw)

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def cache_info(self) -> Dict[str, int]:
        with self._lock:
            return {prog.name: prog.executions for prog in self._cache.values()}

    def compile_table(self) -> Dict[str, Any]:
        """The compile cache as an operator table (/debug/engine): one row
        per program NAME (shape/K variants of the same program aggregate,
        with a `variants` count), plus cache-wide totals. The hit ratio is
        in-memory hits over all compile() lookups — disk loads count as
        misses for the in-memory cache but are reported separately."""
        with self._lock:
            programs = list(self._cache.values())
        by_name: Dict[str, Dict[str, Any]] = {}
        for prog in programs:
            row = by_name.setdefault(prog.name, {
                "name": prog.name, "variants": 0, "executions": 0,
                "cache_hits": 0, "compile_seconds": 0.0,
                "disk_loads": 0})
            row["variants"] += 1
            row["executions"] += prog.executions
            row["cache_hits"] += prog.hits
            row["compile_seconds"] += prog.compile_seconds
            row["disk_loads"] += 1 if prog.source == "disk" else 0
        rows = sorted(by_name.values(),
                      key=lambda r: (-r["compile_seconds"], r["name"]))
        for row in rows:
            row["compile_seconds"] = round(row["compile_seconds"], 3)
        hits = sum(r["cache_hits"] for r in rows)
        lookups = hits + len(programs)
        return {
            "programs": rows,
            "distinct_programs": len(programs),
            "compile_seconds_total": round(
                sum(p.compile_seconds for p in programs), 3),
            "cache_hits_total": hits,
            "disk_hits_total": self.disk_hits,
            "hit_ratio": round(hits / lookups, 4) if lookups else 0.0,
        }
