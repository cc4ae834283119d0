"""Fingerprint-keyed compile cache: N same-shape jobs, one XLA compile.

The sweep scheduler (runtime/sweep.py) runs many jobs whose configs
differ only in seed — the same traced program, the same executable. XLA
compilation is the dominant fixed cost of a small/medium run (the
BENCH_r05 null came from one compile blowing the whole budget), so the
service compiles each distinct world ONCE and reuses the executable
across every batch that shares it:

  * the user-facing key is the config fingerprint **modulo seed**
    (config/fingerprint.py `config_fingerprint(cfg, exclude_seed=True)`)
    plus the batch replica count and rounds_per_chunk — what the sweep
    spec can distinguish;
  * the cache appends the state's shape/dtype signature and the
    canonicalized static EngineConfig (engine/state.py trace_static_cfg)
    to every key, so even a too-coarse caller key can never alias two
    different programs — a mismatch compiles a second entry instead of
    running the wrong executable;
  * entries are AOT-compiled (engine/ensemble.py lower_ensemble_chunk →
    .compile()), so "compile" is an explicit, timed event: `misses`
    counts real XLA compiles, `hits` counts executables reused, and the
    sweep manifest publishes both (the tier-1 test asserts an 8-job
    sweep pays exactly one).

Scope: `CompileCache` is one cache per SweepService (in-process, this
run). `PersistentCompileCache` extends it with a disk tier for the
daemon (runtime/daemon.py, docs/service.md "Daemon mode"): AOT
executables are serialized (jax.experimental.serialize_executable)
into the spool's cache directory keyed by the full cache key PLUS the
jax version and backend platform, so a restarted daemon pays zero XLA
recompiles for worlds it has already compiled — and a corrupt,
truncated, or version-mismatched entry degrades to a recompile with a
warning, never a crash (the `cache-corrupt` chaos fault pins this).

Below both sits JAX's own persistent compilation cache, which serves
every jit of the process (`shadow-tpu run` has no executable tier of its
own). `place_persistent_cache` is the one function that decides its
directory; cli.main calls it before the first compile.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time

import jax

from shadow_tpu.utils.shadow_log import slog

# bumped when the on-disk entry layout changes; a mismatch is a skip
# (recompile), never an error
CACHE_FORMAT = 2


def place_persistent_cache() -> "str | None":
    """Decide where JAX's persistent compilation cache lives — the only
    place in the program that does. Where JAX_COMPILATION_CACHE_DIR is
    set, JAX reads it itself and nothing is set in code (returns None);
    where it is not, the cache goes to `<checkout>/.jax_cache`
    (git-ignored): a fixed path, never a temp name, pid or time, because
    the path is part of what makes the next process find the entries.
    Every compile is kept, however short, so a second run of the same
    command adds no entry (chip_smoke.py checks exactly that). Whether the
    cache is used at all stays JAX's own switch
    (jax_enable_compilation_cache; the tests turn it off)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def state_signature(st) -> tuple:
    """Shape/dtype signature of a state pytree — the part of the jit
    cache key the fingerprint does not cover once buffers have been
    regrown past their config values (rollback-and-regrow)."""
    leaves = jax.tree.leaves(st)
    sig = []
    for l in leaves:
        try:
            sig.append((tuple(l.shape), str(l.dtype)))
        except (AttributeError, TypeError):
            sig.append((None, str(type(l).__name__)))
    return tuple(sig)


class CompileCache:
    """Executable cache + compile accounting for chunk programs.

    `get(key, st, build)` returns the cached executable for
    (key, shapes(st), static cfg) or compiles one via `build()`
    (timed, counted as a miss). `stats()` is the block the sweep
    manifest publishes.
    """

    def __init__(self):
        self._entries: dict = {}
        self.hits = 0
        self.misses = 0
        self.compile_seconds = 0.0
        self.compile_walls: "list[float]" = []
        # memory observatory: XLA-reported peak HBM per compiled entry
        # (memory_analysis is best-effort — backends that don't report it
        # simply leave this list shorter than compile_walls)
        self.compile_peaks: "list[int]" = []

    def _full_key(self, key, st, static_cfg) -> tuple:
        return (key, static_cfg, state_signature(st))

    def get(self, key, st, static_cfg, build):
        """The executable for this (caller key, state shapes, static
        cfg), compiling at most once per distinct full key. `build()`
        must return the callable executable (e.g.
        lower_ensemble_chunk(...).compile())."""
        from shadow_tpu.runtime import flightrec

        fk = self._full_key(key, st, static_cfg)
        exe = self._entries.get(fk)
        if exe is not None:
            self.hits += 1
            flightrec.record_event("compile_cache", hit=True)
            return exe
        exe = self._load_persisted(fk)
        if exe is not None:
            # a disk hit is a hit — the whole point is zero recompiles
            # across daemon restarts. It stays on probation until its
            # first call returns: an entry that loads but cannot run
            # degrades to a recompile like every other bad entry.
            self.hits += 1
            exe = _OnProbation(self, fk, exe, build)
            self._entries[fk] = exe
            flightrec.record_event("compile_cache", hit=True, tier="disk")
            return exe
        return self._compile(fk, build)

    def _compile(self, fk, build):
        from shadow_tpu.runtime import flightrec, memtrack

        t0 = time.perf_counter()
        exe = build()
        wall = time.perf_counter() - t0
        self.misses += 1
        self.compile_seconds += wall
        self.compile_walls.append(round(wall, 4))
        self._entries[fk] = exe
        # compile telemetry: a miss's XLA wall — and, where the backend
        # reports it, the executable's peak HBM (runtime/memtrack.py) —
        # is a first-class event in the metrics stream
        ev = {"hit": False, "wall_s": round(wall, 4)}
        mem = memtrack.compiled_memory(exe)
        if mem and mem.get("peak_bytes"):
            self.compile_peaks.append(int(mem["peak_bytes"]))
            ev["peak_hbm_bytes"] = int(mem["peak_bytes"])
        flightrec.record_event("compile_cache", **ev)
        self._persist(fk, exe)
        return exe

    # the disk-tier seams PersistentCompileCache fills in
    def _load_persisted(self, fk):
        return None

    def _persist(self, fk, exe) -> None:
        pass

    def _loaded_cannot_run(self, fk, err) -> None:
        pass

    @property
    def compiles(self) -> int:
        return self.misses

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        out = {
            "compiles": self.misses,
            "hits": self.hits,
            "hit_rate": round(self.hit_rate(), 4),
            "compile_seconds": round(self.compile_seconds, 4),
            "compile_walls": self.compile_walls,
        }
        if self.compile_peaks:
            out["peak_hbm_bytes"] = max(self.compile_peaks)
            out["compile_peaks"] = self.compile_peaks
        return out


class _OnProbation:
    """A disk-loaded executable until its first call has returned. If
    that call raises, the entry is evicted with the usual warning, the
    program is compiled afresh (a counted miss) and the call is made
    again on the new executable; after one good call the wrapper steps
    aside. A failure of the retry propagates as what it is."""

    def __init__(self, cache: CompileCache, fk, exe, build):
        self._cache, self._fk, self._exe, self._build = cache, fk, exe, build
        self._proven = False

    def __call__(self, *args):
        if self._proven:
            return self._exe(*args)
        try:
            out = self._exe(*args)
        except Exception as e:  # noqa: BLE001 — any failure = recompile
            self._cache._loaded_cannot_run(self._fk, e)
            self._exe = self._cache._compile(self._fk, self._build)
            out = self._exe(*args)
        self._proven = True
        self._build = None  # the closure holds the caller's whole state
        self._cache._entries[self._fk] = self._exe
        return out


class PersistentCompileCache(CompileCache):
    """CompileCache with a disk tier under `cache_dir` (the daemon's
    cross-restart cache).

    Entry layout: one file per full key, named by the sha-256 of the
    key's repr. The file is a one-line JSON header — format version,
    `jax.__version__` + backend platform (a serialized executable is
    only loadable by the runtime that wrote it), and the sha-256 of the
    payload — followed by the pickled
    `jax.experimental.serialize_executable.serialize(exe)` triple.
    Writes are atomic (tmp + rename, the journal/checkpoint idiom).

    Every degradation is survivable BY CONSTRUCTION: an unreadable,
    truncated, digest-mismatched, or version-mismatched entry — and a
    backend whose executables refuse to (de)serialize at all — logs one
    warning and falls back to a normal XLA compile; a bad entry is also
    evicted so the recompile re-stores it. A daemon FLEET shares one
    cache_dir: `_persist` keeps a peer's already-committed entry instead
    of overwriting it (counted as a peer skip). `stats()` gains a
    `persistent` block (disk_hits / disk_stores / disk_skips /
    disk_peer_skips)."""

    def __init__(self, cache_dir: str):
        super().__init__()
        self.cache_dir = cache_dir
        self.disk_hits = 0
        self.disk_stores = 0
        self.disk_skips = 0  # corrupt/mismatched/unserializable entries
        self.disk_peer_skips = 0  # stores skipped: a fleet peer beat us
        self.runtime_version = f"jax-{jax.__version__}/{jax.default_backend()}"
        os.makedirs(cache_dir, exist_ok=True)

    def _entry_path(self, fk) -> str:
        digest = hashlib.sha256(repr(fk).encode()).hexdigest()
        return os.path.join(self.cache_dir, f"exe-{digest[:32]}.bin")

    def _evict(self, path: str) -> None:
        """Drop a bad entry so the recompile's `_persist` re-stores a
        fresh copy instead of peer-skipping the corpse (a fleet shares
        this directory — the existence check must mean 'good entry')."""
        try:
            os.remove(path)
        except OSError:
            pass

    def _load_persisted(self, fk):
        from jax.experimental import serialize_executable

        path = self._entry_path(fk)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                header = json.loads(f.readline())
                payload = f.read()
        except (OSError, ValueError):
            self.disk_skips += 1
            self._evict(path)
            slog("warning", 0, "cache",
                 f"persistent compile-cache entry {path} is unreadable "
                 "(corrupt or truncated); recompiling")
            return None
        if header.get("format") != CACHE_FORMAT or (
            header.get("runtime") != self.runtime_version
        ):
            self.disk_skips += 1
            self._evict(path)
            slog("warning", 0, "cache",
                 f"persistent compile-cache entry {path} was written by "
                 f"{header.get('runtime')!r} format {header.get('format')!r} "
                 f"(this runtime is {self.runtime_version!r} format "
                 f"{CACHE_FORMAT}); recompiling")
            return None
        if hashlib.sha256(payload).hexdigest() != header.get("sha256"):
            self.disk_skips += 1
            self._evict(path)
            slog("warning", 0, "cache",
                 f"persistent compile-cache entry {path} failed its "
                 "sha-256 integrity check; recompiling")
            return None
        try:
            # the executable runs on the devices it was compiled for:
            # left to its default, deserialize_and_load spreads a
            # one-device program over every visible device, and the first
            # call then fails on the shard count
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in header["devices"]]
            serialized, in_tree, out_tree = pickle.loads(payload)
            exe = serialize_executable.deserialize_and_load(
                serialized, in_tree, out_tree, execution_devices=devices
            )
        except Exception as e:  # noqa: BLE001 — any load failure = recompile
            self.disk_skips += 1
            self._evict(path)
            slog("warning", 0, "cache",
                 f"persistent compile-cache entry {path} failed to "
                 f"deserialize ({type(e).__name__}: {str(e)[:120]}); "
                 "recompiling")
            return None
        self.disk_hits += 1
        return exe

    def _loaded_cannot_run(self, fk, err) -> None:
        path = self._entry_path(fk)
        self.disk_hits -= 1
        self.hits -= 1
        self.disk_skips += 1
        self._evict(path)
        slog("warning", 0, "cache",
             f"persistent compile-cache entry {path} loaded but failed "
             f"its first call ({type(err).__name__}: {str(err)[:120]}); "
             "recompiling")

    def _persist(self, fk, exe) -> None:
        from jax.experimental import serialize_executable

        from shadow_tpu.runtime import chaos

        path = self._entry_path(fk)
        if os.path.exists(path):
            # a fleet peer sharing this cache_dir stored the entry while
            # we were compiling (we raced past _load_persisted before it
            # landed); any existing entry passed its own integrity gates
            # when written, and corrupt ones are evicted on load — keep it
            self.disk_peer_skips += 1
            return
        try:
            payload = pickle.dumps(serialize_executable.serialize(exe))
            devices = [d.id for d in exe.runtime_executable().local_devices()]
        except Exception as e:  # noqa: BLE001 — persistence is best-effort
            self.disk_skips += 1
            slog("warning", 0, "cache",
                 f"executable for key {repr(fk)[:60]}… does not serialize "
                 f"on this backend ({type(e).__name__}: {str(e)[:120]}); "
                 "it will be recompiled after a restart")
            return
        header = {
            "format": CACHE_FORMAT,
            "runtime": self.runtime_version,
            "sha256": hashlib.sha256(payload).hexdigest(),
            "bytes": len(payload),
            "devices": devices,
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(json.dumps(header).encode() + b"\n")
                f.write(payload)
            os.replace(tmp, path)
        except OSError as e:
            self.disk_skips += 1
            slog("warning", 0, "cache",
                 f"could not persist compile-cache entry {path}: {e}")
            return
        self.disk_stores += 1
        # chaos seam (runtime/chaos.py `cache-corrupt`): damage lands
        # AFTER the atomic commit — bit-rot on a fully written entry,
        # which is exactly what the sha-256 check must catch
        if chaos.fire("cache-corrupt", at=self.disk_stores - 1) is not None:
            chaos.damage_file(path, truncate=False)

    def stats(self) -> dict:
        out = super().stats()
        out["persistent"] = {
            "dir": self.cache_dir,
            "runtime": self.runtime_version,
            "disk_hits": self.disk_hits,
            "disk_stores": self.disk_stores,
            "disk_skips": self.disk_skips,
            "disk_peer_skips": self.disk_peer_skips,
        }
        return out
