"""The executable store: warm-up's COMPILED executables, kept beside
JAX's persistent cache and served from what was loaded.

JAX computes its persistent cache's key from the lowered module, so a
start that finds every executable in that cache has still traced and
lowered each of them first — a quarter to a half of a warm start's
`setup_s` (PERF.md section 6, PR 62 and PR 63). This store keys an
executable by what the process can see BEFORE it traces anything, so a
second start reads the entry, hands it to PjRt and dispatches through
the loaded `jax.stages.Compiled` — at warm-up and at every serving call.

- **The key** (`ExecutableStore.key_text`, hashed for the file's name and
  kept whole in the entry): a digest of every `polykey_tpu/**/*.py` by
  its path inside the package (never the checkout's), jax / jaxlib and
  the runtime's own version string, platform and device kind, the
  engine's devices in mesh order, `XLA_FLAGS` / `LIBTPU_INIT_ARGS` and
  the JAX settings that change a lowering, the step's name, its static
  arguments as the call passes them, and the tree structure and every
  leaf's aval, sharding and committedness of its dynamic arguments.
  Anything the key cannot vouch for reads as another key: a miss.
- **An entry** is one pickle: the serialized executable
  (`jax.experimental.serialize_executable`, PjRt's own serialization, so
  Mosaic kernels, donation and a mesh's device assignment ride in it),
  its two tree structures, and what warm-up reads off a built step for
  `stats()` (Mosaic calls by kernel name, collectives on a mesh). Written
  under a temporary name and renamed, so a reader never sees half of one.
  Unpickling runs code: the directory is trusted exactly as JAX's cache
  beside it is — bytes this program wrote. An executable that XLA did not
  compile in the very call that stores it (JAX's cache handed it over: the
  start after an upgrade) is kept only where the runtime re-serializes
  what it loaded — the TPU's does, XLA:CPU's does not (`_reserializes`).
- **`StoredStep`** stands where the jitted step stood (`engine._jit_*`):
  a call whose statics and argument shapes were warmed dispatches through
  the table's `Compiled`; any other, or one the executable refuses, goes
  to the jitted function as before and is counted (`fallback_calls`).

No store where there is no cache directory (`config.compile_cache_dir`:
`POLYKEY_COMPILE_CACHE=0`, or a process that placed none): the engine
then holds the jitted functions themselves, as it always did.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import os
import pickle
import threading
import time
from typing import Optional

import jax
import jaxlib
from jax.experimental import serialize_executable
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

FORMAT = 1
SUBDIRECTORY = "executables"
_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The settings of JAX that change what a step lowers to and are not an
# argument of it (the x64 switch and the default precision are the two a
# deployment sets; the others change the random bits or the promotion).
_LOWERING_SETTINGS = (
    "jax_enable_x64", "jax_default_matmul_precision",
    "jax_default_prng_impl", "jax_threefry_partitionable",
    "jax_numpy_dtype_promotion",
)
_LOWERING_ENVIRONMENT = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")


@functools.cache
def source_digest(root: str = _PACKAGE_ROOT) -> str:
    """sha256 over every `*.py` under `root`, by its path inside `root`
    and its bytes, in sorted order; read once a process. A path outside
    the package is no part of it: two checkouts of one tree agree."""
    digest = hashlib.sha256()
    for directory, subdirectories, files in os.walk(root):
        subdirectories.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(directory, name)
            digest.update(os.path.relpath(path, root).encode())
            digest.update(b"\0")
            with open(path, "rb") as f:
                digest.update(f.read())
            digest.update(b"\0")
    return digest.hexdigest()


def _describe_mesh(mesh: Mesh) -> str:
    ids = [int(d.id) for d in mesh.devices.flat]
    return (f"Mesh({mesh.axis_names}, {tuple(mesh.devices.shape)}, "
            f"ids={ids}, types={mesh.axis_types})")


def _describe_sharding(sharding) -> str:
    if sharding is None:
        return "host"
    if isinstance(sharding, NamedSharding):
        return (f"Named({_describe_mesh(sharding.mesh)}, {sharding.spec}, "
                f"{sharding.memory_kind})")
    if isinstance(sharding, SingleDeviceSharding):
        (device,) = sharding.device_set
        return f"Single({int(device.id)}, {sharding.memory_kind})"
    ids = sorted(int(d.id) for d in sharding.device_set)
    return f"{sharding!r} ids={ids}"


def _describe_static(value) -> str:
    """A static argument as the key holds it: its repr (a frozen
    dataclass of plain fields says every one of them; an object with no
    repr of its own says its address, which no later process repeats — a
    miss, never a wrong hit), a mesh with its devices' ids."""
    return _describe_mesh(value) if isinstance(value, Mesh) else repr(value)


def _shapes(args: tuple) -> tuple:
    """What tells one warmed call of a step from another with the same
    statics: the shapes of its top-level array arguments (a prefill's
    rows and bucket, a merge's rows). A tree argument reads None."""
    return tuple(getattr(arg, "shape", None) for arg in args)


class ExecutableStore:
    """One engine's view of `<cache directory>/executables`: reads and
    writes entries for the engine's `devices`, counts what it did
    (`counts`), and says each kind of failure once on `logger`."""

    def __init__(self, directory: str, devices, logger=None):
        self.directory = os.path.join(directory, SUBDIRECTORY)
        self._devices = list(devices)
        self._logger = logger
        self._lock = threading.Lock()
        self._said: set = set()
        self._faithful: Optional[bool] = None
        self._counts = {
            "loaded": 0, "built": 0, "unreadable": 0, "fallback_calls": 0,
            "load_s": 0.0, "store_s": 0.0, "bytes": 0,
        }
        first = self._devices[0]
        settings = {name: getattr(jax.config, name, None)
                    for name in _LOWERING_SETTINGS}
        environment = {name: os.environ.get(name, "")
                       for name in _LOWERING_ENVIRONMENT}
        self._environment = "\n".join((
            f"format {FORMAT}",
            f"sources {source_digest()}",
            f"jax {jax.__version__} jaxlib {jaxlib.__version__}",
            f"runtime {first.client.platform_version}",
            f"platform {first.platform} kind {first.device_kind}",
            f"devices {[int(d.id) for d in self._devices]}",
            f"settings {settings}",
            f"environment {environment}",
        ))

    def counts(self) -> dict:
        """`loaded` / `built`: warm-up executables read from the store /
        lowered, compiled and written to it; `unreadable`: entries found
        and not usable (each rebuilt); `fallback_calls`: serving calls
        that went to the jitted function; `load_s` / `store_s`: seconds
        reading and loading / serializing and writing; `bytes`: of the
        entries read or written."""
        with self._lock:
            out = dict(self._counts)
        out["load_s"], out["store_s"] = (
            round(out["load_s"], 6), round(out["store_s"], 6))
        return out

    def _add(self, **amounts) -> None:
        with self._lock:
            for name, amount in amounts.items():
                self._counts[name] += amount

    def _say_once(self, what: str, **fields) -> None:
        """One WARN line a kind of event (`what`, with the step where the
        fields name one): the counts say how often."""
        with self._lock:
            kind = (what, fields.get("step"))
            first = kind not in self._said
            # polylint: disable=ML002(keyed by kind of event and step: three messages, eight steps)
            self._said.add(kind)
        if first and self._logger is not None:
            self._logger.warn(what, directory=self.directory, **fields)

    # -- the key ---------------------------------------------------------------

    @staticmethod
    def _describe_leaf(leaf, shardings: dict) -> str:
        sharding = getattr(leaf, "sharding", None)
        # One description a sharding OBJECT (a tree's leaves share a
        # handful): kept with the object, so its id stays its own.
        known = shardings.get(id(sharding))
        if known is None:
            known = shardings[id(sharding)] = (
                sharding, _describe_sharding(sharding))
        aval = jax.typeof(leaf)
        return (f"{aval.str_short()} weak={aval.weak_type} {known[1]} "
                f"committed={getattr(leaf, 'committed', None)}")

    def key_text(self, step: str, statics: dict, args: tuple,
                 kwargs: dict) -> str:
        """Everything an entry is good for, as text: the process's part,
        the step's name and `statics` (name -> value), and the dynamic
        arguments' tree and leaves. No tracing: it reads arguments."""
        leaves, tree = jax.tree.flatten((args, kwargs))
        lines = [self._environment, f"step {step}"]
        lines += [f"static {name} {_describe_static(value)}"
                  for name, value in sorted(statics.items())]
        lines.append(f"tree {tree}")
        shardings: dict = {}
        lines += [self._describe_leaf(leaf, shardings) for leaf in leaves]
        return "\n".join(lines)

    def path_of(self, step: str, key_text: str) -> str:
        digest = hashlib.sha256(key_text.encode()).hexdigest()
        return os.path.join(self.directory, f"{step}-{digest[:40]}.pkl")

    # -- entries ---------------------------------------------------------------

    def load(self, path: str, key_text: str, inspected: bool):
        """(Compiled, entry) of the entry at `path`, or None: no such
        file is a plain miss; a file that does not unpickle, is another
        format or key, lacks the inspection this call needs, or that
        PjRt refuses is `unreadable` — said once, rebuilt by the caller,
        never raised."""
        began = time.monotonic()
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            return self._unreadable(path, e)
        try:
            entry = pickle.loads(raw)
            if entry["format"] != FORMAT or entry["key"] != key_text:
                raise ValueError("an entry of another format or key")
            if inspected and entry["kernels"] is None:
                raise ValueError("an entry stored without its inspection")
            compiled = serialize_executable.deserialize_and_load(
                entry["executable"], entry["in_tree"], entry["out_tree"],
                backend=self._devices[0].client,
                execution_devices=self._devices,
            )
        except Exception as e:  # noqa: BLE001 — whatever a bad file raises
            return self._unreadable(path, e)
        self._add(loaded=1, bytes=len(raw),
                  load_s=time.monotonic() - began)
        return compiled, entry

    def _unreadable(self, path: str, error: Exception) -> None:
        self._add(unreadable=1)
        self._say_once(
            "executable store entry unreadable", path=path,
            error=f"{type(error).__name__}: {error}"[:400],
        )
        return None

    def _reserializes(self) -> bool:
        """Whether this runtime serializes an executable it LOADED as
        faithfully as one it compiled, tried once on a small function:
        compiled, then twice through serialize and load, then run.
        XLA:CPU does not (the second serialization drops the compiled
        functions and the load that follows fails at its first dispatch,
        after the pools were donated to it); a runtime that does may
        store what JAX's persistent cache handed it."""
        if self._faithful is None:
            import numpy as np

            device = self._devices[0]
            try:
                x = jax.device_put(np.arange(8, dtype=np.float32), device)
                compiled = jax.jit(
                    lambda x: jax.numpy.tanh(x) * 3.0 + 1.0,
                ).lower(x).compile()
                want = np.asarray(compiled(x))
                for _ in range(2):
                    compiled = serialize_executable.deserialize_and_load(
                        *serialize_executable.serialize(compiled),
                        backend=device.client, execution_devices=[device])
                self._faithful = bool(
                    np.array_equal(np.asarray(compiled(x)), want))
            except Exception:  # noqa: BLE001 — a refusal is the answer
                self._faithful = False
        return self._faithful

    def write(self, path: str, key_text: str, compiled, kernels,
              collectives, first_hand: bool) -> None:
        """Keep a built step: serialize, write beside the final name,
        rename over it (two replicas starting together each write a whole
        file; the later rename wins and both are good). `kernels` and
        `collectives` are warm-up's inspection of it, None where it made
        none. `first_hand`: XLA compiled the executable in
        this very call; one that JAX's persistent cache or the process's
        memory handed over (the store empty or stale beside a warm cache:
        the start after an upgrade) is kept only where the runtime
        re-serializes what it loaded (_reserializes). A step that is not
        kept, does not serialize, or a directory that does not take the
        file, is said once and costs the NEXT start its build — never
        this one."""
        began = time.monotonic()
        self._add(built=1)
        if not first_hand and not self._reserializes():
            self._say_once(
                "executable store entry not written", path=path,
                error="XLA did not compile the executable in this call "
                "(the persistent cache or the process held it) and this "
                "runtime does not re-serialize a loaded one",
            )
            return
        try:
            executable, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            raw = pickle.dumps({
                "format": FORMAT, "key": key_text, "executable": executable,
                "in_tree": in_tree, "out_tree": out_tree,
                "kernels": kernels, "collectives": collectives,
            }, protocol=pickle.HIGHEST_PROTOCOL)
            os.makedirs(self.directory, exist_ok=True)
            partial = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
            try:
                with open(partial, "wb") as f:
                    f.write(raw)
                os.replace(partial, path)
            finally:
                if os.path.exists(partial):
                    os.remove(partial)
        except Exception as e:  # noqa: BLE001 — the store is never fatal
            self._say_once(
                "executable store entry not written", path=path,
                error=f"{type(e).__name__}: {e}"[:400],
            )
            return
        self._add(bytes=len(raw), store_s=time.monotonic() - began)

    def fell_back(self, step: str, reason: Optional[Exception]) -> None:
        self._add(fallback_calls=1)
        self._say_once(
            "call served by the jitted step, not a stored executable",
            step=step,
            reason="no executable was warmed for its statics and shapes"
            if reason is None else f"{type(reason).__name__}: {reason}"[:400],
        )


class StoredStep:
    """A jitted step of the engine, served from the executables its
    warm-up loaded or built. Everything but the call is the jitted
    function's own (`lower`, `_cache_size`, ...)."""

    def __init__(self, store: ExecutableStore, name: str, jitted,
                 static_argnames):
        self._store, self._name, self._jitted = store, name, jitted
        self._static_names = frozenset(static_argnames)
        self._parameters = tuple(inspect.signature(jitted).parameters)
        self._static_positions = tuple(
            i for i, parameter in enumerate(self._parameters)
            if parameter in self._static_names)
        # (statics, shapes) -> Compiled; written at warm-up, read by every
        # serving call, shrunk only where an executable refused a call.
        self._table: dict = {}

    def __getattr__(self, name: str):
        return getattr(self._jitted, name)

    def _split(self, args: tuple, kwargs: dict):
        """(statics by name, dynamic args, dynamic kwargs) of a call, as
        a `Compiled` wants them: the static arguments are not its."""
        positions, names = self._static_positions, self._static_names
        statics = {self._parameters[i]: args[i]
                   for i in positions if i < len(args)}
        dynamic = tuple(arg for i, arg in enumerate(args)
                        if i not in positions)
        dynamic_kwargs = {}
        for name, value in kwargs.items():
            if name in names:
                statics[name] = value
            else:
                dynamic_kwargs[name] = value
        return statics, dynamic, dynamic_kwargs

    @staticmethod
    def _table_key(statics: dict, dynamic: tuple) -> tuple:
        return frozenset(statics.items()), _shapes(dynamic)

    def load(self, args: tuple, kwargs: dict, inspected: bool):
        """Warm-up's first half: (key, inspection) — `inspection` is the
        entry's ({"kernels", "collectives"}) where the store held this
        call's executable and it is installed now, None where the caller
        has to build it and hand it to `keep` with the same `key`."""
        statics, dynamic, dynamic_kwargs = self._split(args, kwargs)
        key_text = self._store.key_text(
            self._name, statics, dynamic, dynamic_kwargs)
        path = self._store.path_of(self._name, key_text)
        key = (path, key_text, self._table_key(statics, dynamic))
        found = self._store.load(path, key_text, inspected)
        if found is None:
            return key, None
        compiled, entry = found
        self._table[key[2]] = compiled
        return key, {"kernels": entry["kernels"],
                     "collectives": entry["collectives"]}

    def keep(self, key: tuple, compiled, first_hand: bool, kernels=None,
             collectives=None) -> None:
        """Warm-up's second half on a miss: install the step the caller
        built and write its entry (ExecutableStore.write)."""
        path, key_text, table_key = key
        self._table[table_key] = compiled
        self._store.write(path, key_text, compiled, kernels, collectives,
                          first_hand)

    def __call__(self, *args, **kwargs):
        statics, dynamic, dynamic_kwargs = self._split(args, kwargs)
        table_key = self._table_key(statics, dynamic)
        compiled = self._table.get(table_key)
        refusal = None
        if compiled is not None:
            try:
                return compiled(*dynamic, **dynamic_kwargs)
            except (TypeError, ValueError) as e:
                # An aval (TypeError) or a sharding (ValueError) that is
                # not what it was built for: raised before anything was
                # dispatched or donated. The jitted step takes the call,
                # and every later one like it.
                refusal = e
                self._table.pop(table_key, None)
        self._store.fell_back(self._name, refusal)
        return self._jitted(*args, **kwargs)
