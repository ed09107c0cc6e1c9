"""Persistent warm-start compile store: the disk tier under the AOT
executable cache (control/aotcache.py).

The in-memory cache pays the first-compile cost once per shape class
*per process*; for a fleet the dominant cold-start cost is exactly that
first process-local compile, multiplied by every live plan a replica
must restore. This module makes the executables themselves durable:
each compiled XLA executable is AOT-serialized
(``jax.experimental.serialize_executable``) to disk under the SAME
cache key the in-memory tier uses, so a fresh replica deserializes and
loads instead of lowering — zero new XLA lowerings on bootstrap, pinned
cross-process by ``metrics()["compiles"]`` (tests/test_fleet.py).

Key soundness is inherited, not re-derived: :func:`aotcache.cache_key`
returns ``("dyn", signature)`` only for single-``DynamicChainGroup``
hosts (constants are device data — signature-equal hosts are
interchangeable programs) and pins the exact source text for everything
else, and a ``None`` key is never stored. On top of that the store
namespaces by accelerator topology (platform, device kind, device
count) and jax version — a serialized executable is a compiled artifact
for one backend; a mismatch is a safe miss, never a wrong program.

Within one cache key, executables are further keyed by the abstract
value signature of their call arguments (shape/dtype/weak_type per
leaf + the pytree structure): the same dispatch-site dispatching the
jit wrapper would do, made explicit so the stored executable for one
state capacity never serves a grown one.

Counters (``hits`` = executables loaded from disk, ``misses`` = AOT
compiles the store had to fall back to, ``persists`` = executables
written) land in the bound registry as ``fleet.warm_hit`` /
``fleet.warm_miss`` / ``fleet.persist`` (OpenMetrics
``fst_fleet_*_total``) and in the flight recorder under the same kinds
(rate-collapsed; telemetry/flightrec.py).
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import re
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from ..control.aotcache import sig_label

_LOG = logging.getLogger(__name__)

# executable bundle slots persisted per cache key (the CachedExecutables
# fields holding jit wrappers); drain pack programs ride separately as
# pack@<width> slots
SLOT_NAMES = (
    "jitted_seg",
    "jitted_init_acc",
    "jitted_flush",
)


def _sanitize(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "_", name)


def store_namespace() -> str:
    """The topology/version namespace every store path lives under. A
    serialized executable is backend- and topology-specific (the test
    environment pins ``xla_force_host_platform_device_count``, so even
    CPU runs have a meaningful device count); two processes agree on
    keys exactly when they agree on this string, and a mismatch
    (upgrade, different accelerator) degrades to a safe cold miss."""
    dev = jax.devices()[0]
    return _sanitize(
        f"{dev.platform}-{getattr(dev, 'device_kind', 'unknown')}"
        f"-n{jax.device_count()}-jax{jax.__version__}"
    )


def store_key_dir(key: Tuple[str, str]) -> str:
    """Directory name for one cache key: kind-prefixed digest of the
    key payload. The kind ("dyn" vs "exact") stays readable so the
    soundness split is visible in a directory listing."""
    digest = hashlib.sha256(key[1].encode("utf-8")).hexdigest()
    return f"{key[0]}-{digest[:40]}"


def aval_signature(args: Tuple) -> str:
    """Stable string signature of a call's abstract values: pytree
    structure + (shape, dtype, weak_type) per leaf. Concrete arrays and
    ``jax.ShapeDtypeStruct`` trees of the same avals produce the same
    signature, so executables warmed from abstract inputs serve
    concrete calls."""
    leaves, treedef = jax.tree.flatten(args)
    parts = [str(treedef)]
    for x in leaves:
        dtype = getattr(x, "dtype", None)
        if dtype is None:
            dtype = np.asarray(x).dtype
        parts.append(
            f"{np.shape(x)}:{np.dtype(dtype)}"
            f":{bool(getattr(x, 'weak_type', False))}"
        )
    return hashlib.sha256("|".join(parts).encode("utf-8")).hexdigest()[:24]


class WarmSlot:
    """One executable slot of a cached bundle, dispatching by aval
    signature: a loaded/stored XLA executable when the signature is
    known, an AOT compile of the wrapped ``jax.jit`` function when it
    is not (counted as a store miss — the same lowering, at the same
    call site, the bare wrapper would have paid). Never wrong: any
    failure to serve a stored executable falls back to the wrapper."""

    def __init__(
        self,
        wrapper: Callable,
        store: "WarmStartStore",
        key: Tuple[str, str],
        slot: str,
    ) -> None:
        self._wrapper = wrapper
        self._store = store
        self._key = key
        self._slot = slot
        # aval signature -> loaded (or fallback-compiled) executable
        # fst:threadsafe GIL-atomic dict get/set; the run loop and the warm-compile pool thread may race one signature — the loser's executable is identical and a lost insert recompiles once
        self._exes: Dict[str, object] = {}
        # aval signature -> serialized payload of an executable compiled
        # here (same threads, same argument); kept, like the executable,
        # so that a store swept by gc() is written again
        self._payloads: Dict[str, bytes] = {}
        self._scope: Dict[str, Optional[str]] = {
            "plan": None, "tenant": None,
        }

    # -- dispatch ---------------------------------------------------------
    def __call__(self, *args):
        sig = aval_signature(args)
        exe = self._exes.get(sig)
        if exe is None:
            exe = self._compile(args, sig)
        try:
            return exe(*args)
        except Exception as e:  # noqa: BLE001 — conservative fallback
            # an executable that refuses its inputs (aval drift the
            # signature failed to separate) must never take the job
            # down: drop it and take the wrapper's ordinary jit path
            _LOG.warning(
                "warm executable %s/%s rejected its inputs (%s: %s); "
                "falling back to the jit wrapper",
                self._slot, sig, type(e).__name__, e,
            )
            self._exes.pop(sig, None)
            self._store._count_error()
            return self._wrapper(*args)

    def lower(self, *args):
        """Shim for the ``fn.lower(*abstract).compile()`` call sites
        (the background flush warmer, executor._warm_flush): returns an
        object whose ``compile()`` serves the stored executable on a
        signature match and captures the compiled fallback otherwise."""
        slot = self

        class _Lowered:
            def compile(self, *a, **kw):
                sig = aval_signature(args)
                exe = slot._exes.get(sig)
                if exe is None:
                    exe = slot._compile(args, sig)
                return exe

        return _Lowered()

    def _compile(self, args, sig: str):
        exe = self._wrapper.lower(*args).compile()
        # serialized here, before its first call: XLA's CPU backend
        # refuses an executable whose sort has run ("`LessThan` is not
        # serializable"). The bytes wait for persist_entry
        payload = self._store._serialize(self._slot, sig, exe)
        if payload is not None:
            self._payloads[sig] = payload
        self._exes[sig] = exe
        self._store._count_miss(
            self._key, self._slot, sig, **self._scope
        )
        return exe

    # -- store plumbing ---------------------------------------------------
    def adopt(self, sig: str, exe) -> None:
        self._exes[sig] = exe

    def payloads(self) -> Dict[str, bytes]:
        return dict(self._payloads)


class WarmStartStore:
    """The on-disk executable store. Layout::

        <root>/<namespace>/<key dir>/<slot>@<aval sig>.exe

    where each ``.exe`` file is the pickled
    ``(serialized_bytes, in_tree, out_tree)`` triple of
    ``jax.experimental.serialize_executable.serialize`` plus the ids of
    the devices the executable was compiled for (it is loaded for
    exactly those — ``deserialize_and_load`` otherwise loads it for
    every device of the backend, and an executable compiled for one
    device then rejects its inputs on a several-device host). Writes are
    atomic (tmp + rename), reads that fail to unpickle or load are
    counted errors and degrade to a miss."""

    def __init__(self, root: str) -> None:
        self.root = os.fspath(root)
        self.namespace = store_namespace()
        self._dir = os.path.join(self.root, self.namespace)
        os.makedirs(self._dir, exist_ok=True)
        self._telemetry = None
        self._flightrec = None
        # fst:threadsafe lock-guarded counters: the run loop (bootstrap/persist) and the warm-compile pool thread (flush fallback) both count
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.persists = 0
        self.errors = 0
        self.evictions = 0

    def bind_telemetry(self, registry) -> None:
        self._telemetry = registry

    def bind_flightrec(self, recorder) -> None:
        self._flightrec = recorder

    # -- accounting -------------------------------------------------------
    def _count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def _inc(self, name: str, n: int = 1) -> None:
        if self._telemetry is not None:
            self._telemetry.inc(name, n)

    def _rec(self, kind: str, key, slot, sig, plan=None, tenant=None):
        if self._flightrec is not None:
            self._flightrec.record(
                kind, plan=plan, tenant=tenant,
                signature=sig_label(key), slot=slot, aval=sig,
            )

    def _count_hit(self, key, slot, sig, plan=None, tenant=None):
        self._count("hits")
        self._inc("fleet.warm_hit")
        self._rec("fleet.warm_hit", key, slot, sig, plan, tenant)

    def _count_miss(self, key, slot, sig, plan=None, tenant=None):
        self._count("misses")
        self._inc("fleet.warm_miss")
        self._rec("fleet.warm_miss", key, slot, sig, plan, tenant)

    def _count_persist(self, key, slot, sig, plan=None, tenant=None):
        self._count("persists")
        self._inc("fleet.persist")
        self._rec("fleet.persist", key, slot, sig, plan, tenant)

    def _count_error(self) -> None:
        self._count("errors")

    def stats(self) -> Dict[str, object]:
        with self._lock:
            return {
                "root": self.root,
                "namespace": self.namespace,
                "hits": self.hits,
                "misses": self.misses,
                "persists": self.persists,
                "errors": self.errors,
                "evictions": self.evictions,
            }

    # -- paths ------------------------------------------------------------
    def key_dir(self, key: Tuple[str, str]) -> str:
        return os.path.join(self._dir, store_key_dir(key))

    def _exe_path(self, key, slot: str, sig: str) -> str:
        return os.path.join(self.key_dir(key), f"{slot}@{sig}.exe")

    # -- raw executable i/o -----------------------------------------------
    def _serialize(self, slot: str, sig: str, compiled) -> Optional[bytes]:
        from jax.experimental import serialize_executable as se

        try:
            device_ids = [
                d.id
                for d in compiled.runtime_executable().local_devices()
            ]
            return pickle.dumps((*se.serialize(compiled), device_ids))
        except Exception as e:  # noqa: BLE001 — best-effort persist
            _LOG.warning(
                "could not serialize %s/%s (%s: %s)",
                slot, sig, type(e).__name__, e,
            )
            self._count_error()
            return None

    def _write_exe(self, key, slot: str, sig: str, payload: bytes) -> bool:
        path = self._exe_path(key, slot, sig)
        if os.path.exists(path):
            return False
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, path)  # atomic: readers never see a torn file
        return True

    def _load_exe(self, key, slot: str, sig_file: str):
        from jax.experimental import serialize_executable as se

        path = os.path.join(self.key_dir(key), sig_file)
        try:
            with open(path, "rb") as f:
                blob, in_tree, out_tree, device_ids = pickle.load(f)
            by_id = {d.id: d for d in jax.devices()}
            return se.deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids],
            )
        except Exception as e:  # noqa: BLE001 — degrade to a miss
            _LOG.warning(
                "warm store entry %s unreadable (%s: %s); cold path",
                path, type(e).__name__, e,
            )
            self._count_error()
            return None

    # -- eviction / garbage collection ------------------------------------
    def _count_evict(self, entry: str, nbytes: int, reason: str):
        self._count("evictions")
        self._inc("fleet.warm_evict")
        if self._flightrec is not None:
            self._flightrec.record(
                "fleet.warm_evict", entry=entry,
                bytes=int(nbytes), reason=reason,
            )

    def _entry_readable(self, path: str) -> bool:
        """Cheap validity probe: the pickled tuple unpickles and its
        first element is the serialized-executable byte blob. Does NOT
        deserialize the XLA executable (that is the load path's job)."""
        try:
            with open(path, "rb") as f:
                blob = pickle.load(f)[0]
            return isinstance(blob, (bytes, bytearray))
        except Exception:  # noqa: BLE001 — any failure = corrupt
            return False

    def gc(
        self,
        max_bytes: Optional[int] = None,
        max_entries: Optional[int] = None,
        sweep_corrupt: bool = True,
    ) -> Dict[str, int]:
        """Bound the store (ROADMAP: eviction policy): size-bounded LRU
        over whole key-dir entries ordered by their newest file mtime
        (an entry any replica recently persisted into is recent), plus
        a sweep of corrupt/torn files — unreadable ``.exe`` payloads
        and leftover ``.tmp-<pid>`` writes. Evicting is always safe:
        a future lookup of an evicted key is an ordinary cold miss that
        recompiles and re-persists (the never-wrong store contract).
        Each removal counts ``fleet.warm_evict`` and journals a
        flight-recorder entry with the reason (``lru``/``corrupt``)."""
        import shutil

        removed_corrupt = 0
        entries = []  # (newest mtime, bytes, dir name, dir path)
        try:
            names = os.listdir(self._dir)
        except OSError:
            names = []
        for name in sorted(names):
            path = os.path.join(self._dir, name)
            if not os.path.isdir(path):
                continue
            size = 0
            newest = 0.0
            for fn in sorted(os.listdir(path)):
                fp = os.path.join(path, fn)
                try:
                    st = os.stat(fp)
                except OSError:
                    continue
                if ".tmp-" in fn:
                    # torn write leftover (a crash between open and the
                    # atomic rename): never referenced, always swept
                    if sweep_corrupt:
                        try:
                            os.unlink(fp)
                        except OSError:
                            continue
                        removed_corrupt += 1
                        self._count_evict(
                            f"{name}/{fn}", st.st_size, "corrupt"
                        )
                    continue
                if (
                    sweep_corrupt
                    and fn.endswith(".exe")
                    and not self._entry_readable(fp)
                ):
                    try:
                        os.unlink(fp)
                    except OSError:
                        continue
                    removed_corrupt += 1
                    self._count_evict(
                        f"{name}/{fn}", st.st_size, "corrupt"
                    )
                    continue
                size += st.st_size
                newest = max(newest, st.st_mtime)
            if not os.listdir(path):
                try:
                    os.rmdir(path)
                except OSError:
                    pass
                continue
            entries.append((newest, size, name, path))
        entries.sort()  # oldest newest-mtime first = LRU order
        total = sum(e[1] for e in entries)
        evicted = 0
        while entries and (
            (max_entries is not None and len(entries) > max_entries)
            or (max_bytes is not None and total > max_bytes)
        ):
            _mt, size, name, path = entries.pop(0)
            shutil.rmtree(path, ignore_errors=True)
            total -= size
            evicted += 1
            self._count_evict(name, size, "lru")
        return {
            "evicted": evicted,
            "corrupt_removed": removed_corrupt,
            "kept": len(entries),
            "bytes": int(total),
        }

    def _listing(self, key) -> Dict[str, list]:
        """slot name -> [aval sig, ...] currently on disk for key."""
        out: Dict[str, list] = {}
        try:
            names = os.listdir(self.key_dir(key))
        except OSError:
            return out
        for name in names:
            if not name.endswith(".exe") or "@" not in name:
                continue
            slot, sig = name[: -len(".exe")].split("@", 1)
            out.setdefault(slot, []).append(sig)
        return out

    # -- bundle-level api (executor integration) --------------------------
    def wrap_entry(
        self, key, entry, plan_id: Optional[str] = None,
        tenant: Optional[str] = None,
    ):
        """Wrap a :class:`CachedExecutables` bundle's jit wrappers in
        :class:`WarmSlot` dispatchers and preload every executable the
        store holds for ``key`` — each load is a warm hit; signatures
        not on disk stay cold and compile (a miss) at their ordinary
        call site. Drain pack programs (``pack<width>`` slots) preload into
        ``entry.pack_jits`` behind the same fallback contract.
        Idempotent: an already-wrapped bundle (in-memory cache hit)
        only refreshes the plan/tenant scope."""
        on_disk = self._listing(key)
        for name in SLOT_NAMES:
            fn = getattr(entry, name)
            if isinstance(fn, WarmSlot):
                fn._scope = {"plan": plan_id, "tenant": tenant}
                continue
            slot = WarmSlot(fn, self, key, name)
            slot._scope = {"plan": plan_id, "tenant": tenant}
            for sig in on_disk.get(name, ()):
                exe = self._load_exe(key, name, f"{name}@{sig}.exe")
                if exe is not None:
                    slot.adopt(sig, exe)
                    self._count_hit(key, name, sig, plan_id, tenant)
            setattr(entry, name, slot)
        for slot_name in on_disk:
            if not slot_name.startswith("pack"):
                continue
            try:
                width = int(slot_name[len("pack"):])
            except ValueError:
                continue
            if width in entry.pack_jits:
                continue
            sig = on_disk[slot_name][0]
            exe = self._load_exe(
                key, slot_name, f"{slot_name}@{sig}.exe"
            )
            if exe is not None:
                entry.pack_jits[width] = _pack_callable(exe, width)
                self._count_hit(key, slot_name, sig, plan_id, tenant)
        return entry

    def persist_entry(
        self, key, entry, acc_example=None,
        plan_id: Optional[str] = None, tenant: Optional[str] = None,
    ) -> int:
        """Write every executable the bundle's warm slots compiled to
        disk (each was serialized as it was compiled, WarmSlot._compile),
        skipping ones already there — persisting at each checkpoint
        boundary is cheap once the store is caught up. Pack
        programs are re-lowered from ``acc_example`` at persist time —
        off the hot path, outside any compile-attribution scope — only
        for widths not on disk yet. Returns how many files were
        written."""
        wrote = 0
        for name in SLOT_NAMES:
            fn = getattr(entry, name)
            if not isinstance(fn, WarmSlot):
                continue
            for sig, payload in fn.payloads().items():
                if self._write_exe(key, name, sig, payload):
                    self._count_persist(key, name, sig, plan_id, tenant)
                    wrote += 1
        if acc_example is not None:
            wrote += self._persist_packs(
                key, entry, acc_example, plan_id, tenant
            )
        return wrote

    def _persist_packs(
        self, key, entry, acc_example, plan_id, tenant
    ) -> int:
        wrote = 0
        sig = aval_signature((acc_example,))
        for width, fn in list(entry.pack_jits.items()):
            slot = f"pack{int(width)}"
            if os.path.exists(self._exe_path(key, slot, sig)):
                continue
            lower = getattr(fn, "lower", None)
            if lower is None:
                continue  # store-loaded callable: already on disk
            try:
                compiled = lower(acc_example).compile()
            except Exception as e:  # noqa: BLE001 — best-effort
                _LOG.debug(
                    "pack width %s not persistable (%s: %s)",
                    width, type(e).__name__, e,
                )
                continue
            payload = self._serialize(slot, sig, compiled)
            if payload is not None and self._write_exe(
                key, slot, sig, payload
            ):
                self._count_persist(key, slot, sig, plan_id, tenant)
                wrote += 1
        return wrote


def _pack_callable(compiled, width: int) -> Callable:
    """A store-loaded drain pack program with the never-wrong fallback:
    a rejected input (accumulator aval drift) rebuilds the same slice
    jit ``Job._pack_data`` would have built lazily."""
    fallback = {}

    def call(a):
        try:
            return compiled(a)
        except Exception:  # noqa: BLE001 — conservative fallback
            fn = fallback.get("fn")
            if fn is None:
                # fst:hotpath
                def pack(acc, _w=width):
                    rows = acc["buf"].shape[0]
                    return jax.lax.slice(
                        acc["buf"], (0, 0), (rows, _w)
                    )

                fn = fallback["fn"] = jax.jit(pack)
            return fn(a)

    return call
