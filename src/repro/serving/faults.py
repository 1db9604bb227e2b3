"""Deterministic fault injection for the serving stack (DESIGN.md §13).

``FaultInjector`` wraps any ``Executor`` behind the same interface
(decorator pattern) and injects configurable faults at the poll/launch
boundaries the real failure modes surface through:

* **transient launch faults** (``TransientLaunchError``) — a round launch
  raises before any state is committed; the executor contract assigns
  ``pool.state`` only after a successful call, so retrying the launch is
  free (zero recomputation) and byte-identical.
* **injected compile failures** (``InjectedCompileError``) — the lazy AOT
  compile path raising at first call of an executable.
* **persistent device-lost** (``DeviceLostError``) — after
  ``device_lost_after`` launches every subsequent launch on this injector
  raises, forever: the scheduler's only way out is failover to a fresh
  executor.
* **corrupted done-mask reads** — ``done_mask`` returns a mask with one
  lane flipped; a re-read returns the true value (transient read
  corruption, recovered by ``recovery.verified_read``).
* **poison** (``PoisonError``) — the ``poison_nth_install``-th lane ever
  installed is fingerprinted, and any round on a pool currently hosting
  that fingerprint raises, every time.  Poison follows the *request data*
  (the context fingerprint), not the lane index, so evict/requeue cannot
  shake it off — only quarantine isolates it.

Every fault site draws from its own deterministic schedule:
``u01(f"{seed}:{site}:{count}")`` (a sha256-derived uniform) with a
per-site call counter, so two runs of the same request stream against
the same plan inject the identical fault sequence — chaos tests are
exactly reproducible (``tests/test_faults.py`` asserts this).

All of it is OFF by default: a server built without a ``FaultPlan`` never
constructs an injector and its execution path is byte-identical to
pre-fault-subsystem behavior.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np
import jax

from repro.serving.executor import Executor, LanePool


# -- exception taxonomy -------------------------------------------------
class FaultError(RuntimeError):
    """Base class for injected (and injectable) serving faults.  The
    default ``RetryPolicy.retry_on`` is ``(FaultError,)``; operators
    broaden it to real backend exception types in production."""


class TransientLaunchError(FaultError):
    """A round launch failed before committing any state; retryable."""


class InjectedCompileError(FaultError):
    """An executable's AOT compile failed; retryable (the cache never
    keeps an entry for a failed compile — see ``serving.cache``)."""


class DeviceLostError(FaultError):
    """The executor's device is gone, persistently.  NOT retryable on the
    same executor: the scheduler fails over to a fresh one."""


class PoisonError(FaultError):
    """A request resident in this pool deterministically kills every
    round.  Retry cannot help; quarantine bisection isolates it."""


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """What to inject, when.  All rates are per-call probabilities drawn
    from the per-site deterministic schedule; everything defaults OFF."""

    seed: int = 0
    launch_rate: float = 0.0        # P(TransientLaunchError) per round launch
    compile_rate: float = 0.0       # P(InjectedCompileError) per round launch
    corrupt_done_rate: float = 0.0  # P(one flipped lane) per done_mask read
    device_lost_after: int | None = None   # launches before permanent death
    poison_nth_install: int | None = None  # 1-based lane-install ordinal to
    #                                        mark as poison (None = no poison)


def u01(key: str) -> float:
    """Deterministic uniform draw in [0, 1) from a string key.  sha256,
    not ``random.Random(key).random()``: the Mersenne Twister's FIRST
    output after seeding with near-identical strings (the per-site
    ``f"{seed}:{site}:{n}"`` keys differ only in the trailing counter)
    is visibly correlated — runs of small values appear at rates far
    above chance, which made a 15% fault schedule fire 5x consecutively
    and spuriously quarantine healthy requests.  A cryptographic hash
    has no such neighborhood structure, and is stable across platforms
    and processes."""
    h = hashlib.sha256(key.encode()).digest()
    return int.from_bytes(h[:8], "big") / 2.0 ** 64


def fingerprint(tree) -> str:
    """Content hash of a pytree (sha1 over the raw bytes of every leaf).
    Used to make poison follow the request's *data* across installs,
    evictions and executor failover — the injector never sees rids."""
    h = hashlib.sha1()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


class FaultInjector(Executor):
    """Executor decorator injecting the ``FaultPlan``'s faults.

    The wrapped executor is untouched: every interface method delegates,
    with injection layered on ``run_round`` (launch faults, device-lost,
    poison), ``done_mask`` (read corruption), ``install``/``restore``
    (poison fingerprinting) and ``big_lane`` (the returned lane is
    proxied so the big route shares the launch-fault schedule).

    ``n_injected`` counts every injected fault and ``log`` records them
    as ``(site, ordinal, kind)`` dicts — the reproducibility surface the
    chaos determinism test compares across runs.
    """

    def __init__(self, inner: Executor, plan: FaultPlan,
                 _poison_fps: set[str] | None = None):
        self.inner = inner
        self.plan = plan
        self.name = f"fault({inner.name})"
        self.n_injected = 0
        self.log: list[dict] = []
        self._site_counts: dict[str, int] = {}
        self._launches = 0              # global launch-attempt ordinal
        self._dead = False              # device-lost latched
        self._installs = 0              # global lane-install ordinal
        self._poison_fps: set[str] = (_poison_fps if _poison_fps is not None
                                      else set())
        # poisoned lane indices per live pool; LanePool has __slots__ (no
        # attribute bag, no weakrefs) so marks live here, keyed by id()
        self._marks: dict[int, set[int]] = {}

    # -- schedule -------------------------------------------------------
    def _fire(self, site: str, rate: float) -> bool:
        """One draw from ``site``'s deterministic schedule."""
        if rate <= 0.0:
            return False
        n = self._site_counts.get(site, 0)
        self._site_counts[site] = n + 1
        return u01(f"{self.plan.seed}:{site}:{n}") < rate

    def _record(self, site: str, kind: str) -> None:
        self.n_injected += 1
        self.log.append(dict(site=site, n=self._site_counts.get(site, 0),
                             kind=kind))

    def _launch_gate(self, site: str, poisoned: bool) -> None:
        """The per-launch injection point shared by pool rounds and the
        big-graph lane; raises in severity order."""
        if self._dead:
            raise DeviceLostError(
                "injected device-lost (persistent): executor "
                f"{self.inner.name!r} is gone")
        n = self._launches
        self._launches += 1
        dla = self.plan.device_lost_after
        if dla is not None and n >= dla:
            self._dead = True
            self._record(site, "DeviceLostError")
            raise DeviceLostError(
                f"injected device-lost at launch #{n} (persistent)")
        if poisoned:
            self._record(site, "PoisonError")
            raise PoisonError(
                f"injected poison: a poisoned request is resident ({site})")
        if self._fire(site, self.plan.launch_rate):
            self._record(site, "TransientLaunchError")
            raise TransientLaunchError(
                f"injected transient launch fault ({site}, launch #{n})")
        if self._fire(f"{site}:compile", self.plan.compile_rate):
            self._record(site, "InjectedCompileError")
            raise InjectedCompileError(
                f"injected compile failure ({site}, launch #{n})")

    def for_failover(self, inner: Executor) -> "FaultInjector":
        """The injector for the post-failover executor: same transient
        rates (chaos continues), but the device-lost clock and the poison
        install trigger are disarmed — already-recorded poison
        fingerprints are SHARED, so a poisoned request stays poisoned
        across failover and still has to be quarantined."""
        plan = dataclasses.replace(self.plan, device_lost_after=None,
                                   poison_nth_install=None)
        return FaultInjector(inner, plan, _poison_fps=self._poison_fps)

    # -- lane planning / placement (pure delegation) --------------------
    def plan_lanes(self, n_pending, policy):
        return self.inner.plan_lanes(n_pending, policy)

    def placement(self, n_lanes):
        return self.inner.placement(n_lanes)

    def launches_per_segment(self, pool):
        return self.inner.launches_per_segment(pool)

    def stepwise(self, pool):
        return self.inner.stepwise(pool)

    def _pool_sharding(self):
        return self.inner._pool_sharding()

    # -- pool lifecycle (delegation + poison bookkeeping) ----------------
    def new_pool(self, cfg, n_lanes, engine=None):
        pool = self.inner.new_pool(cfg, n_lanes, engine)
        self._marks[id(pool)] = set()
        return pool

    def _mark(self, pool, idx, ctxs) -> None:
        """Fingerprint each installed context.  A host context's bytes are
        those of the device context it becomes, so poison marked here
        follows the request onto the big-graph lane too."""
        marks = self._marks.setdefault(id(pool), set())
        for i, ctx in zip(idx, ctxs):
            self._installs += 1
            fp = fingerprint(ctx)
            if self.plan.poison_nth_install == self._installs:
                self._poison_fps.add(fp)
                self._record("install", "poison-marked")
            if fp in self._poison_fps:
                marks.add(i)
            else:
                marks.discard(i)

    def install(self, pool, idx, ctxs, n_tasks, cache):
        self._mark(pool, idx, ctxs)
        return self.inner.install(pool, idx, ctxs, n_tasks, cache)

    def restore(self, pool, idx, ctxs, states):
        self._mark(pool, idx, ctxs)
        return self.inner.restore(pool, idx, ctxs, states)

    def migrate(self, old, new, live_idx):
        old_marks = self._marks.get(id(old), set())
        self._marks[id(new)] = {j for j, i in enumerate(live_idx)
                                if i in old_marks}
        return self.inner.migrate(old, new, live_idx)

    def evict(self, pool, i):
        self._marks.setdefault(id(pool), set()).discard(i)
        return self.inner.evict(pool, i)

    # -- execution ------------------------------------------------------
    def run_round(self, pool, cache, budget, unroll=1):
        self._launch_gate(f"launch[{pool.cfg.n_u}x{pool.cfg.n_v}]",
                          poisoned=bool(self._marks.get(id(pool))))
        return self.inner.run_round(pool, cache, budget, unroll)

    # -- demux views ----------------------------------------------------
    def lane(self, pool, i):
        return self.inner.lane(pool, i)

    def done_mask(self, pool: LanePool) -> np.ndarray:
        mask = self.inner.done_mask(pool)
        if self._fire("done_mask", self.plan.corrupt_done_rate) \
                and mask.size:
            n = self._site_counts["done_mask"]
            j = int(u01(f"{self.plan.seed}:done_mask_idx:{n}")
                    * mask.size)
            self._record("done_mask", "corrupted-read")
            mask = mask.copy()
            mask[j] = ~mask[j]
        return mask

    def steps(self, pool):
        return self.inner.steps(pool)

    # -- big-graph lane -------------------------------------------------
    def big_lane(self, cfg, ctx, n_roots, cache, budget, engine=None,
                 steps_per_call=1):
        lane = self.inner.big_lane(cfg, ctx, n_roots, cache, budget,
                                   engine=engine,
                                   steps_per_call=steps_per_call)
        poisoned = fingerprint(ctx) in self._poison_fps
        return _InjectedBigLane(self, lane, poisoned)


class _InjectedBigLane:
    """Proxy over a ``BigGraphLane`` so the big route draws from the same
    launch-fault schedule (site ``"big"``); everything else delegates."""

    def __init__(self, injector: FaultInjector, lane, poisoned: bool):
        self._injector = injector
        self._lane = lane
        self._poisoned = poisoned

    def run_round(self):
        self._injector._launch_gate("big", poisoned=self._poisoned)
        return self._lane.run_round()

    def __getattr__(self, attr):
        return getattr(self._lane, attr)
