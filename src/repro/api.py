"""One front door: the ``MBEClient`` unified enumeration API.

The repo grew four divergent entry points — ``enumerate_dense`` /
``enumerate_compact`` (single graph, exact shape), the distributed runner
in ``launch/mbe_run.py`` (the paper's one-big-graph decomposition), and
``MBEServer.admit``/``poll`` (the many-graphs serving layer) — each with
its own configuration knobs, and the compact array that is cuMBE's core
contribution reachable only from tests and benchmarks.  This module is
the single public surface over all of them:

    from repro import MBEClient, MBEOptions

    client = MBEClient(MBEOptions(engine="compact", collect=True,
                                  collect_cap=64))
    res = client.enumerate(graph)               # sync, one graph
    print(res.n_max, res.bicliques)

    results = client.enumerate_many(graphs)     # batched stream

    fut = client.submit(graph, priority=5, deadline_s=30.0)
    ...                                         # admit more, poll, etc.
    if not fut.done():
        fut.cancel()                            # or fut.result(timeout=60)

``MBEOptions`` is ONE dataclass subsuming the knobs that used to be
hand-wired across three modules (``BucketPolicy`` shape/batching fields,
``EngineConfig`` ordering/collect fields, executor mesh placement, and
the big-graph routing threshold), and it selects the execution path:

* ``mesh=None``                 — local single-device vmap lane pools.
* ``mesh=N`` / ``mesh="auto"``  — lane pools sharded over a 1-D serving
  mesh of N (or all visible) host devices.
* ``big_graph_threshold=K``     — requests with >= K root tasks route to
  the work-stealing big-graph lane (the paper's decomposition); with
  ``big_graph_threshold=1`` every request takes that path, which is how
  ``launch/mbe_run.py`` serves one big graph end to end.
* ``engine="dense" | "compact" | "count" | "mce"`` — any engine
  registered in ``repro.core.engine`` (``repro.engines()`` lists them);
  the compact array, the (p,q)-biclique counter and the unipartite
  maximal-clique engine all serve through the exact same
  bucket/cache/executor stack.  Each engine returns its own
  ``EngineResult`` variant (``MBEResult`` / ``CountResult`` /
  ``CliqueResult``); ``result.metric`` is the engine-agnostic headline
  scalar.

Request lifecycle (DESIGN.md §7): pending -> placed -> running ->
{done, cancelled, timed_out, failed, step_capped}.  ``MBEFuture.cancel()``
removes a pending request before anything compiles, or evicts an
in-flight lane via row surgery; an expired ``deadline_s`` completes the
request with ``result.timed_out == True``; a request quarantined by the
fault-tolerance subsystem (``MBEOptions.retry``, DESIGN.md §13)
completes with ``status == "failed"`` and a ``fail_reason``; a request
hitting ``max_graph_steps`` completes with ``status == "step_capped"``
(unless ``strict_step_cap=True`` restores the legacy raise).  Flagged
results carry the partial counters made before eviction and
``bicliques=None``.

The client is a facade over one ``MBEServer`` — ``client.server`` is the
escape hatch, and ``MBEServer.admit/poll/drain/flush/serve`` remain
supported for existing callers.
"""
from __future__ import annotations

import dataclasses
import time

from repro.core.engine import Engine, get_engine, list_engines
from repro.core.graph import BipartiteGraph, unipartite_graph
from repro.core.results import (CliqueResult, CountResult, EngineResult,
                                MBEResult)
from repro.serving import (AdmissionController, AdmissionPolicy,
                           BucketPolicy, ExecutableCache, FaultPlan,
                           LocalExecutor, MBEServer, RetryPolicy,
                           ShardedExecutor, imbalance)


def engines() -> list[str]:
    """Names of every registered engine — what ``MBEOptions(engine=...)``
    and the launchers' ``--engine`` flags accept (``repro.core.engine``
    registry, built-ins included)."""
    return list_engines()


@dataclasses.dataclass(frozen=True)
class MBEOptions:
    """Every knob of the enumeration service, in one place.

    Grouped the way the old modules split them; each field documents
    which subsystem consumes it.  The defaults reproduce the historical
    ``MBEServer()`` behaviour: dense engine, pow2 buckets, one local
    device, whole-batch rounds, no routing, no collection.
    """

    # -- engine (repro.core.engine registry) ---------------------------
    engine: str = "dense"         # 'dense' | 'compact' | 'count' | 'mce'
    #                               | any registered name (repro.engines()
    #                               lists them; unknown names raise
    #                               ValueError at options construction)
    count_p: int = 2              # the count engine's (p, q): count
    count_q: int = 2              # (p,q)-bicliques = K_{p,q} subgraphs.
    #                               Inert for enumeration engines; rides
    #                               EngineConfig.count_pq into the
    #                               executable-cache key
    order_mode: str = "deg"       # candidate ordering (EngineConfig)
    impl: str = "jnp"             # intersect_count impl (unfused path)
    kernel_impl: str = "auto"     # step-kernel path ('auto'|'jnp'|
    #                               'pallas'): 'pallas' runs the fused
    #                               fused_select/fused_check Pallas
    #                               kernels (one adjacency pass per
    #                               branch; interpret mode off-TPU),
    #                               'auto' picks pallas on TPU and jnp
    #                               elsewhere (kernels.dispatch)
    collect: bool = False         # decode bicliques into results
    collect_cap: int = 1          # collect buffer rows per lane
    resident_lanes: int | str = "auto"   # multi-lane resident pool
    #                               kernel (kernels.resident_pool) on the
    #                               pallas+resident path: 'auto' = one
    #                               launch per pool whenever the per-cell
    #                               VMEM gate admits it; int k >= 2 caps
    #                               the pool width; 0/1 pins the legacy
    #                               one-launch-per-lane vmap layout

    # -- shape bucketing / batching (serving.buckets.BucketPolicy) -----
    bucket_mode: str = "pow2"     # 'pow2' | 'linear' | 'exact'
    step_u: int = 8               # linear-mode granularity, U side
    step_v: int = 32              # linear-mode granularity, V side
    min_u: int = 4                # bucket floors
    min_v: int = 16
    max_batch: int = 8            # lanes per pool
    pad_batch: bool = True        # pow2 lane counts (executable reuse)

    # -- scheduling (serving.scheduler.MBEServer) ----------------------
    steps_per_round: int = 0      # 0 = whole-batch rounds; > 0 = bounded
    #                               rounds with mid-flight lane refill
    steps_per_call: int = 1       # engine-loop inner unroll: candidate
    #                               steps per while-loop iteration inside
    #                               one compiled round segment (byte-
    #                               identical; amortizes per-step loop
    #                               dispatch — BucketPolicy.steps_per_call)
    big_graph_threshold: int | None = None   # route >= K root tasks to
    #                               the work-stealing big-graph lane
    max_graph_steps: int | None = None       # per-graph step cap
    cache_capacity: int | None = ExecutableCache.DEFAULT_CAPACITY

    # -- SLO layer (serving.slo; DESIGN.md §12) -------------------------
    admission: AdmissionPolicy | None = None  # admission control in
    #                               front of the pending queues:
    #                               bounded-queue backpressure, weighted
    #                               per-tenant fairness, shed-on-deadline
    #                               (refused requests complete with
    #                               status == "rejected" instead of
    #                               burning compile/step budget).  None
    #                               = admit everything (byte-identical
    #                               to the pre-SLO server)
    trace_path: str | None = None  # record a JSONL request trace
    #                               (admit/result/poll events) for the
    #                               replay simulator and policy planner;
    #                               None = no tracing, no extra branch

    # -- fault tolerance (serving.faults/recovery; DESIGN.md §13) -------
    retry: RetryPolicy | None = None     # retry / checkpoint / quarantine
    #                               / failover policy.  None (default) =
    #                               no recovery machinery, byte-identical
    #                               serving; a failed round then raises as
    #                               it always did
    fault_injector: FaultPlan | None = None  # deterministic fault
    #                               injection for chaos testing: wraps the
    #                               executor in a FaultInjector driven by
    #                               the plan's seed + rates.  None = no
    #                               wrapper at all
    strict_step_cap: bool = False  # True restores the legacy behaviour of
    #                               max_graph_steps: evict capped lanes
    #                               then RAISE RuntimeError.  False (the
    #                               new default) completes capped requests
    #                               with status == "step_capped" carrying
    #                               their partial counters

    # -- placement (serving.executor) ----------------------------------
    mesh: int | str | None = None  # None = one local device; N = 1-D
    #                                serving mesh over N host devices;
    #                                "auto" = every visible device
    workers_per_device: int = 1   # big-lane stealing workers per device
    #                               (sharded executor over-decomposition)
    big_workers: int = 4          # big-lane vmap workers (local executor)
    work_stealing: bool = True    # False = the paper's noWS ablation on
    #                               the big-graph lane

    # ------------------------------------------------------------------
    def __post_init__(self):
        get_engine(self.engine)     # unknown engine names fail HERE, at
        #                             options construction, with a
        #                             ValueError naming the available
        #                             engines — not at first submit

    def engine_params(self) -> dict:
        """Engine-specific ``EngineConfig`` parameters threaded through
        ``MBEServer._engine_config`` into every bucket config (and thus
        every executable-cache key).  Engines ignore parameters they do
        not consume."""
        return dict(count_pq=(self.count_p, self.count_q))

    def bucket_policy(self) -> BucketPolicy:
        return BucketPolicy(
            mode=self.bucket_mode, step_u=self.step_u, step_v=self.step_v,
            min_u=self.min_u, min_v=self.min_v, max_batch=self.max_batch,
            pad_batch=self.pad_batch, steps_per_round=self.steps_per_round,
            steps_per_call=self.steps_per_call,
            big_graph_threshold=self.big_graph_threshold)

    def make_executor(self):
        if self.mesh is None:
            return LocalExecutor(big_workers=self.big_workers,
                                 work_stealing=self.work_stealing)
        from repro.sharding.axes import mbe_serve_mesh
        n = None if self.mesh == "auto" else int(self.mesh)
        return ShardedExecutor(
            mbe_serve_mesh(n),
            big_workers_per_device=self.workers_per_device,
            work_stealing=self.work_stealing)

    def make_server(self) -> MBEServer:
        return MBEServer(
            self.bucket_policy(), collect_cap=self.collect_cap,
            collect=self.collect, order_mode=self.order_mode,
            impl=self.impl, kernel_impl=self.kernel_impl,
            max_graph_steps=self.max_graph_steps,
            executor=self.make_executor(),
            cache_capacity=self.cache_capacity,
            engine=get_engine(self.engine),
            engine_params=self.engine_params(),
            resident_lanes=self.resident_lanes,
            admission=self.admission,
            trace_path=self.trace_path,
            retry=self.retry,
            fault_injector=self.fault_injector,
            strict_step_cap=self.strict_step_cap)


class MBEFuture:
    """Handle for one submitted request.

    Single-process cooperative future: ``result()`` drives the client's
    scheduling loop (``server.poll``) until this request completes, so
    other in-flight requests make progress while you wait.  ``done()``
    and ``cancel()`` never run a scheduling round.

    The terminal result is *claimed* by the future on first
    retrieval: it moves out of the client's mailbox onto the future
    object (``result()`` stays idempotent), so a long-lived client only
    holds results whose futures have not been asked yet.
    """

    __slots__ = ("_client", "rid", "name", "_result")

    def __init__(self, client: "MBEClient", rid: int, name: str):
        self._client = client
        self.rid = rid
        self.name = name
        self._result: EngineResult | None = None

    def _claim(self) -> EngineResult | None:
        if self._result is None:
            res = self._client._mailbox.pop(self.rid, None)
            if res is not None:
                self._result = res
                self._client._watched.discard(self.rid)
        return self._result

    def done(self) -> bool:
        """Whether a terminal result (done/cancelled/timed_out) is
        available."""
        if self._claim() is not None:
            return True
        self._client._harvest()
        return self._claim() is not None

    def cancel(self) -> bool:
        """Cancel the request: pending requests are dropped before any
        compile, in-flight requests have their lane evicted and refilled.
        Returns False when the result already exists (too late)."""
        if self.done():
            return False
        ok = self._client.server.cancel(self.rid)
        self._client._harvest()
        return ok

    def result(self, timeout: float | None = None) -> EngineResult:
        """Block until the request reaches a terminal state and return its
        result (check ``result.status`` — a cancelled or
        deadline-expired request returns a flagged result rather than
        raising).  ``timeout`` bounds the wait in seconds; on expiry the
        request keeps running and ``TimeoutError`` is raised."""
        t0 = time.perf_counter()
        while True:
            if self.done():
                return self._result
            if not self._client.server.has_work():
                raise KeyError(
                    f"request {self.rid} is unknown to the server "
                    f"(no pending work and no stashed result)")
            if timeout is not None \
                    and time.perf_counter() - t0 > timeout:
                raise TimeoutError(
                    f"request {self.rid} ({self.name}) not done within "
                    f"{timeout}s (still being served; cancel() to stop)")
            self._client.poll()

    def __repr__(self) -> str:
        state = "pending"
        if self._result is not None \
                or self.rid in self._client._mailbox:
            state = "done"
        return f"<MBEFuture rid={self.rid} {self.name!r} {state}>"


class MBEClient:
    """The single public entry point for maximal biclique enumeration.

    One client owns one ``MBEServer`` (and therefore one executable
    cache, one executor, one set of lane pools); submit any mix of
    graphs and the scheduler buckets, batches, routes and refills
    underneath.  See ``MBEOptions`` for the execution-path knobs and the
    module docstring for usage.
    """

    def __init__(self, options: MBEOptions | None = None, **overrides):
        if options is None:
            options = MBEOptions(**overrides)
        elif overrides:
            options = dataclasses.replace(options, **overrides)
        self.options = options
        self.server = options.make_server()
        # mailbox: terminal results awaiting their future's first
        # retrieval.  Only rids with an outstanding (unclaimed) future are
        # retained — completion batches delivered to direct poll()/drain()
        # callers pass through without accumulating — so the client's
        # footprint is bounded by the futures the caller is still holding.
        self._mailbox: dict[int, EngineResult] = {}
        self._watched: set[int] = set()
        # completion sink: results land in the mailbox at delivery time no
        # matter WHO drove the scheduling loop — futures stay coherent
        # even when the low-level server surface is driven directly
        self.server.add_completion_sink(self._on_complete)

    # ------------------------------------------------------------------
    def _on_complete(self, batch: dict[int, EngineResult]) -> None:
        for rid, res in batch.items():
            if rid in self._watched:
                self._mailbox[rid] = res

    def _harvest(self) -> None:
        self.server.reap()          # stashed results flow through the sink

    def submit(self, g: BipartiteGraph, priority: int = 0,
               deadline_s: float | None = None,
               tenant: str = "default") -> MBEFuture:
        """Enqueue one graph; returns an ``MBEFuture``.  ``priority``
        reorders placement within a bucket (higher first); ``deadline_s``
        bounds the request's wall-clock lifetime; ``tenant`` is the
        accounting + fairness identity (``stats()['per_tenant']``, the
        admission controller's weighted queue shares).  With
        ``MBEOptions.admission`` set the request may be refused here —
        its future then resolves to a result with
        ``status == "rejected"`` (check ``result.reject_reason``)."""
        rid = self.server.admit(g, priority=priority,
                                deadline_s=deadline_s, tenant=tenant)
        self._watched.add(rid)
        return MBEFuture(self, rid, g.name)

    def enumerate(self, g: BipartiteGraph, priority: int = 0,
                  deadline_s: float | None = None) -> EngineResult:
        """Synchronous single-graph enumeration through the serving
        stack (byte-identical to the engine's direct ``enumerate``)."""
        return self.submit(g, priority=priority,
                           deadline_s=deadline_s).result()

    def enumerate_many(self, graphs: list[BipartiteGraph]
                       ) -> list[EngineResult]:
        """Batched enumeration of a whole stream; results in submit
        order.  Shapes are bucketed so the stream shares executables."""
        futs = [self.submit(g) for g in graphs]
        self.server.drain()
        return [f.result() for f in futs]

    def poll(self) -> dict[int, EngineResult]:
        """One scheduling round; returns the requests that completed this
        round (results for outstanding futures are also kept claimable)."""
        return self.server.poll()

    def drain(self) -> dict[int, EngineResult]:
        """Serve everything pending; returns everything that completed."""
        return self.server.drain()

    # ------------------------------------------------------------------
    @property
    def routing_log(self) -> list[dict]:
        return self.server.routing_log

    def stats(self) -> dict:
        """Server stats plus the client-level load-balance summary:
        ``big_imbalance`` is max/mean per-worker busy steps on the
        big-graph lane (``serving.imbalance`` — the zero-guarded metric
        ``launch/mbe_run.py`` reports)."""
        return self.server.stats()


__all__ = ["MBEClient", "MBEFuture", "MBEOptions", "MBEResult",
           "EngineResult", "CountResult", "CliqueResult", "engines",
           "unipartite_graph", "imbalance", "Engine", "get_engine",
           "list_engines"]
