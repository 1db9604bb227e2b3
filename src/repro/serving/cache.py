"""Compiled-executable cache for the serving layer.

One cache entry per executable identity: for the local backend that is
``(EngineConfig, batch_size, round_budget)``; the sharded and work-stealing
backends prepend their placement (mesh + axis + workers-per-device) to the
config slot, so one server process can serve the same bucket through
different backends without the entries colliding.  Each entry owns its own
``jax.jit`` wrapper with every shape pinned, so entry creation corresponds
1:1 to an XLA compilation on first call and the hit/miss counters are an
honest compile count (``jax.jit``'s internal per-shape cache never silently
recompiles behind a "hit").

Entry flavours sharing the cache:

* **drain entries** (``round_budget=None``) run a batch to completion —
  the whole-batch flush path.
* **round entries** (``round_budget=k``) bound every call to ``k`` engine
  steps per lane, so the continuous scheduler can demux finished lanes and
  refill them between rounds.  Because the budget is part of the key, a
  continuous stream costs exactly ONE round-mode compile per
  ``(bucket, batch)`` pair, no matter how many rounds it runs.
* **backend entries** (via ``get_entry``) wrap an arbitrary jitted
  function — the ``ShardedExecutor``'s mesh-placed ``shard_map`` round,
  the big-graph lane's work-stealing round, and each local pool's
  fixed-shape install executable (``Executor.install``).  AOT compile
  timing works the same way for every backend: the entry times its own
  ``lower().compile()``.

Entries also time their own XLA compilation: the first call AOT-lowers and
compiles (``jit.lower(...).compile()``) with ``time.perf_counter`` around
it, so schedulers can report ``compile_s`` separately instead of folding a
first-call compile into some unlucky request's service latency.

**Capacity** — the cache is an LRU bounded at ``capacity`` entries (a
policy knob, default generous: a long-lived server sees a handful of
buckets x batch sizes x backends, nowhere near the default).  Without the
bound, a server fed adversarial or drifting shape traffic would accrete
compiled executables forever; with it, the coldest entry is dropped and
honestly recompiled if that shape ever returns (``evictions`` in
``stats()`` counts the drops).

This is what turns shape bucketing into throughput: a mixed stream of
requests collapses onto a handful of entries, amortizing compilation
across every graph that ever lands in the same bucket.
"""
from __future__ import annotations

import collections
import time
from typing import Callable

import jax

from repro.core import engine_dense as ed
from repro.core.engine import DENSE, Engine


class CacheEntry:
    """One batched enumeration executable, lazily AOT-compiled.

    Calling the entry the first time lowers + compiles the jitted function
    (timed into ``compile_s``), then runs the compiled executable; later
    calls go straight to the compiled object.  ``compile_s`` stays 0.0
    until the first call and is never charged twice.

    A FAILED compile commits nothing: ``_compiled`` stays ``None``,
    ``compile_s`` stays 0.0, and the owning ``ExecutableCache`` is told
    (via the ``on_failed`` hook) to drop the entry and roll back its miss
    count — so a compile failure can neither leave a poisoned entry in
    the cache nor inflate the compile counter.  If the SAME entry object
    is later called again and compiles successfully (a retry), the
    ``on_compiled`` hook re-commits it, so the cache and its counters end
    up exactly as if the failure never happened.
    """

    __slots__ = ("_jit", "_compiled", "compile_s", "_on_compiled",
                 "_on_failed")

    def __init__(self, fn, on_compiled=None, on_failed=None):
        self._jit = fn
        self._compiled = None
        self.compile_s = 0.0
        self._on_compiled = on_compiled
        self._on_failed = on_failed

    @property
    def compiled(self) -> bool:
        return self._compiled is not None

    def __call__(self, *args):
        if self._compiled is None:
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("mbe.compile"):
                    compiled = self._jit.lower(*args).compile()
            except Exception:
                if self._on_failed is not None:
                    self._on_failed(self)
                raise
            self.compile_s = time.perf_counter() - t0
            self._compiled = compiled
            if self._on_compiled is not None:
                self._on_compiled(self)
        return self._compiled(*args)

    def timed_call(self, ctx: ed.GraphContext, s: ed.DenseState):
        """Blocking call with the round-accounting split every backend
        needs: returns ``(out, wall_s, compile_s)`` where ``wall_s`` is
        the full blocked wall time and ``compile_s`` is the XLA compile
        charged to THIS call (0.0 whenever the entry was already
        compiled — compilation is never billed twice).  The blocked
        interval is the host span ``mbe.round.wait`` (``mbe.compile``
        nested inside on a first call)."""
        was_compiled = self.compiled
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("mbe.round.wait"):
            out = jax.block_until_ready(self(ctx, s))
        wall = time.perf_counter() - t0
        return out, wall, (0.0 if was_compiled else self.compile_s)


class ExecutableCache:
    """LRU cache of ``CacheEntry`` objects, bounded at ``capacity``."""

    DEFAULT_CAPACITY = 256

    def __init__(self, capacity: int | None = DEFAULT_CAPACITY):
        if capacity is not None and capacity < 1:
            raise ValueError(f"capacity must be >= 1 or None, got {capacity}")
        self.capacity = capacity
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # ------------------------------------------------------------------
    def get_entry(self, key, build: Callable[[], object]) -> CacheEntry:
        """Generic keyed lookup: on miss, ``build()`` must return a jitted
        function (a round's ``(ctx, state) -> ...``, or a pool's install
        executable) which is wrapped in a lazily AOT-compiled
        ``CacheEntry``.  Executors use this to register their
        backend-specific executables under backend-qualified keys.

        Compile-failure safety: the entry is inserted (and the miss
        counted) here, but if its first AOT compile RAISES the entry is
        evicted and the miss rolled back (``_discard``), so a failed
        compile never leaves a poisoned entry and the miss count stays an
        honest count of successful compiles.  A later request for the
        key builds afresh; a retry of the same entry object re-commits on
        success (``_commit``)."""
        entry = self._entries.get(key)
        if entry is not None:
            self.hits += 1
            self._entries.move_to_end(key)      # LRU touch
            return entry
        self.misses += 1
        entry = CacheEntry(build(),
                           on_compiled=lambda e: self._commit(key, e),
                           on_failed=lambda e: self._discard(key, e))
        self._entries[key] = entry
        if self.capacity is not None and len(self._entries) > self.capacity:
            self._entries.popitem(last=False)   # drop the coldest
            self.evictions += 1
        return entry

    def _discard(self, key, entry: CacheEntry) -> None:
        """Compile failed: drop the entry (only if it is still the
        resident one — it may have been LRU-evicted meanwhile) and roll
        back the miss, so ``misses`` never counts a failed compile."""
        if self._entries.get(key) is entry:
            del self._entries[key]
            self.misses = max(self.misses - 1, 0)

    def _commit(self, key, entry: CacheEntry) -> None:
        """Successful compile: ensure the entry holds a slot (it is a
        no-op on the normal path where ``get_entry`` already inserted it;
        it re-inserts after a failure rollback when the same entry object
        was retried and succeeded).  If ANOTHER entry took the key in the
        meantime, the incumbent wins — no overwrite, no double count."""
        if key in self._entries:
            return
        self.misses += 1
        self._entries[key] = entry
        if self.capacity is not None and len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.evictions += 1

    def get_round(self, cfg: ed.EngineConfig, batch: int,
                  max_steps: int | None = None,
                  engine: Engine | None = None,
                  unroll: int = 1) -> CacheEntry:
        """Local-backend batched enumeration executable: (ctx, state) ->
        state, where all leaves carry a leading axis of size ``batch``.
        ``max_steps`` bounds every lane to that many engine steps per call
        (None = run to completion); it is baked into the executable, hence
        part of the cache key, as is ``unroll`` (the multi-step
        compiled-segment knob, ``BucketPolicy.steps_per_call``).
        ``engine`` selects the enumeration engine (``repro.core.engine``
        registry; default dense).  The dense engine keeps the legacy
        bare-``EngineConfig`` key; other engines qualify the config slot
        with their name — ``EngineConfig`` is shared between engines, so
        an unqualified compact entry would collide with the dense
        executable for the same bucket.  Likewise ``unroll=1`` keeps the
        legacy 3-slot key, and a ``("pool", width)`` slot is appended
        ONLY when the engine's multi-lane pool path is active for this
        (cfg, batch) — legacy keys stay byte-for-byte stable."""
        eng = engine or DENSE

        def build():
            @jax.jit
            def fn(ctx, s):
                return eng.run_batch(ctx, cfg, s, max_steps=max_steps,
                                     ctx_batched=True, unroll=unroll)
            return fn

        head = cfg if eng.name == DENSE.name else (eng.name, cfg)
        key = (head, batch, max_steps) if unroll == 1 \
            else (head, batch, max_steps, unroll)
        pw = eng.pool_lanes(cfg, batch)
        if pw:
            key = key + (("pool", pw),)
        return self.get_entry(key, build)

    def get(self, cfg: ed.EngineConfig, batch: int) -> CacheEntry:
        """Run-to-completion executable (drain entry)."""
        return self.get_round(cfg, batch, None)

    def stats(self) -> dict:
        return dict(hits=self.hits, misses=self.misses,
                    entries=len(self._entries), evictions=self.evictions)

    def reset_counters(self) -> None:
        """Zero the monotonic hit/miss/eviction counters WITHOUT touching
        the entries themselves (``MBEServer.reset_stats`` uses this to
        separate warmup compiles from a measured phase — the miss count
        stays an honest compile count *per phase*; ``entries`` is a gauge
        and still reports the live executables)."""
        self.hits = 0
        self.misses = 0
        self.evictions = 0
