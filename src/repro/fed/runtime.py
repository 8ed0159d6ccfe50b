"""Zero-copy engine runtime: donated carries, persistent compile cache,
async checkpoint/transfer pipelining (DESIGN.md §15).

PRs 6–7 made the per-round *math* fast; this module makes the runtime
around the compiled programs hot-path too.  Four pieces, shared by both
engines (``fed/scan_engine.py``, ``fed/engine.py``) and the service
front-end (``launch/serve.py``):

``enable_compile_cache()``
    Wires ``jax``'s persistent compilation cache
    (``jax.experimental.compilation_cache``) so a re-launched sweep or a
    second service process pays XLA compile once per (program, device
    topology) — entries are keyed by XLA on the optimized HLO + compile
    options + backend, so heterogeneous programs never collide.  Thresholds
    are dropped to cache-everything: the engine's programs are few and
    re-compiled from scratch they dominate warm-start latency.  The one way
    to place the cache is ``JAX_COMPILATION_CACHE_DIR``; unset, it lives at
    the fixed ``<repo>/.jax_cache``.  Only the command-line entry points
    turn it on — never an import or an engine constructor.

``ProgramCache``
    A bounded LRU over the engines' jitted programs (the old ``_jits``
    dict grew unboundedly across heterogeneous sweeps) with hit / miss /
    eviction / compile-event counters.  Compile time is measured per call:
    a call that grows the underlying jit's executable cache is a compile
    event and its wall-clock (trace + lower + XLA or persistent-cache
    load; dispatch is async so steady-state calls return in ~µs) is
    recorded as ``compile_ms`` — this is what splits first-call compile
    from steady-state run in the benches.

``CarryHandle``
    The donation-safety audit.  ``jax.jit(..., donate_argnums=...)`` frees
    the scan carry's input buffers for in-place reuse; a caller that still
    holds the old carry would read garbage (or, on backends that implement
    donation, trip a late "Array has been deleted").  Every carry the
    engine hands out is wrapped in a handle that is invalidated the moment
    a donated program consumes it — use-after-donation is a LOUD,
    immediate ``RuntimeError`` on every backend, not a heisenbug.

``AsyncCheckpointWriter``
    One background thread, bounded queue, strict submission order: npz
    checkpoint serialization + disk write overlap the next segment's
    device compute instead of blocking the dispatch loop.  ``close()``
    drains the queue and re-raises the first worker error so failures are
    never silent.
"""
from __future__ import annotations

import os
import queue
import threading
import time
import warnings
from collections import OrderedDict
from pathlib import Path
from typing import Callable, Optional

import jax

# Backends that cannot honor a donation simply keep the copy and warn;
# the engine's semantics (CarryHandle consume-once) are identical either
# way, so the warning is noise — donation is best-effort by design.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


# ------------------------------------------------------- persistent cache
REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The persistent compile cache's directory: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<repo>/.jax_cache``.  The path is part of
    the cache key, so it is never derived from a temporary name, a process
    id or the time."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at ``compile_cache_dir()``
    (created if missing) and drop the size/time thresholds so every engine
    program is cached.  Idempotent; returns the directory.  Keying (trust
    the cache): XLA fingerprints the optimized HLO module + compile options
    + backend/topology, so a program compiled for one device count never
    serves another."""
    cache_dir = compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != cache_dir:
        # the persistent-cache layer initializes ONCE per process, at the
        # first compile — if that happened before this call (or with a
        # different dir), the config update alone is a silent no-op; reset
        # so the next compile re-initializes against cache_dir
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )
        _cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


# ----------------------------------------------------------- program LRU
class _TimedProgram:
    """Wraps one jitted callable; detects compile events by watching the
    jit executable-cache size across calls (dispatch is async, so a timed
    call that did NOT compile returns in dispatch time, while a compile
    call pays trace + lower + XLA / persistent-cache load)."""

    def __init__(self, fn, stats: dict):
        self._fn = fn
        self._stats = stats

    def __call__(self, *args, **kwargs):
        probe = getattr(self._fn, "_cache_size", None)
        before = probe() if probe is not None else -1
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        if probe is not None and probe() > before:
            self._stats["compiles"] += 1
            self._stats["compile_ms"] += (time.perf_counter() - t0) * 1e3
        return out

    def __getattr__(self, name):          # .lower(...) etc. pass through
        return getattr(self._fn, name)


class ProgramCache:
    """Bounded LRU of compiled programs keyed on static config, with
    hit / miss / eviction / compile counters — the replacement for the
    engines' unbounded ``_jits`` dicts."""

    def __init__(self, maxsize: int = 32):
        if maxsize < 1:
            raise ValueError(f"ProgramCache needs maxsize >= 1, "
                             f"got {maxsize}")
        self.maxsize = int(maxsize)
        self._programs: OrderedDict = OrderedDict()
        self._stats = {"hits": 0, "misses": 0, "evictions": 0,
                       "compiles": 0, "compile_ms": 0.0}

    def __len__(self) -> int:
        return len(self._programs)

    def __contains__(self, key) -> bool:
        return key in self._programs

    def get(self, key, build: Callable[[], Callable]):
        """The program for ``key``, building (and possibly evicting the
        least-recently-used entry) on miss."""
        if key in self._programs:
            self._stats["hits"] += 1
            self._programs.move_to_end(key)
            return self._programs[key]
        self._stats["misses"] += 1
        prog = _TimedProgram(build(), self._stats)
        self._programs[key] = prog
        while len(self._programs) > self.maxsize:
            self._programs.popitem(last=False)
            self._stats["evictions"] += 1
        return prog

    def stats(self) -> dict:
        """Counters snapshot: hits, misses, evictions, compiles,
        compile_ms (sum over compile events), size."""
        return {**self._stats, "size": len(self._programs)}


# ------------------------------------------------------- donated carries
class CarryHandle:
    """Ownership token for a (possibly donated) device carry pytree.

    ``tree`` reads without consuming (host gathers for checkpoints);
    ``consume()`` surrenders the buffers to a donated program and
    invalidates the handle.  Any later access raises immediately —
    the loud-error half of the donation contract (DESIGN.md §15)."""

    __slots__ = ("_tree", "_alive", "_label")

    def __init__(self, tree, label: str = "scan carry"):
        self._tree = tree
        self._alive = True
        self._label = label

    @property
    def alive(self) -> bool:
        return self._alive

    @property
    def tree(self):
        if not self._alive:
            raise RuntimeError(
                f"use-after-donation: this {self._label} handle was "
                f"consumed by a donated program (jit donate_argnums) and "
                f"its buffers now belong to that program's output. Use the "
                f"handle RETURNED by run_segment / the stream, not the one "
                f"you passed in.")
        return self._tree

    def consume(self):
        """Surrender the carry to a donated call: returns the pytree and
        invalidates the handle."""
        tree = self.tree
        self._alive = False
        self._tree = None
        return tree


# -------------------------------------------------- async checkpoint I/O
class AsyncCheckpointWriter:
    """Single worker thread executing submitted thunks in order, so npz
    serialization + disk writes overlap device compute.  The queue is
    bounded (backpressure: a sweep that outruns the disk blocks on submit
    instead of accumulating whole trajectories in host memory).  Errors
    are sticky: the first worker exception is re-raised on the next
    ``submit``/``flush``/``close``."""

    def __init__(self, max_pending: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max_pending)
        self._err: Optional[BaseException] = None
        # backpressure visibility (DESIGN.md §17): queue high-watermark,
        # total time submit() spent BLOCKED on a full queue, and worker
        # write time — surfaced through the engines' runtime_stats()
        self._stats = {"submitted": 0, "completed": 0, "max_pending":
                       int(max_pending), "queue_high_watermark": 0,
                       "blocked_ms": 0.0, "write_ms": 0.0}
        self._thread = threading.Thread(
            target=self._loop, name="ckpt-writer", daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                if self._err is None:     # fail-fast: skip after first error
                    fn, args, kwargs = item
                    t0 = time.perf_counter()
                    fn(*args, **kwargs)
                    self._stats["write_ms"] += \
                        (time.perf_counter() - t0) * 1e3
                    self._stats["completed"] += 1
            except BaseException as e:    # noqa: BLE001 — re-raised on host
                self._err = e
            finally:
                self._q.task_done()

    def _raise_pending(self):
        if self._err is not None:
            err, self._err = self._err, None
            raise RuntimeError("async checkpoint write failed") from err

    def submit(self, fn: Callable, *args, **kwargs):
        self._raise_pending()
        item = (fn, args, kwargs)
        try:
            self._q.put_nowait(item)
        except queue.Full:
            # the backpressure path: the producer outran the disk — time
            # the stall so it shows up in runtime_stats / BENCH rows
            t0 = time.perf_counter()
            self._q.put(item)
            self._stats["blocked_ms"] += (time.perf_counter() - t0) * 1e3
        self._stats["submitted"] += 1
        self._stats["queue_high_watermark"] = max(
            self._stats["queue_high_watermark"], self._q.qsize())

    def stats(self) -> dict:
        """Counters snapshot + instantaneous queue depth."""
        return {**self._stats, "queue_depth": self._q.qsize(),
                "blocked_ms": round(self._stats["blocked_ms"], 3),
                "write_ms": round(self._stats["write_ms"], 3)}

    def flush(self):
        """Block until everything submitted so far has been written."""
        self._q.join()
        self._raise_pending()

    def close(self):
        """Drain, stop the worker, and surface any write error."""
        self._q.join()
        self._q.put(None)
        self._thread.join()
        self._raise_pending()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # drain on clean exit; on error, still stop the thread but prefer
        # the caller's exception over a secondary writer error
        try:
            self.close()
        except RuntimeError:
            if exc_type is None:
                raise
        return False
