"""Kernel microbenchmark: the fused step-kernel path vs the unfused ops.

Four levels, all emitted into one ``--json`` artifact (``BENCH_8.json``
is the committed baseline — the perf trajectory for the enumeration hot
step):

* **op level** — one candidate-branch worth of work at a benchmark shape:
  ``unfused`` = ``intersect_count`` + the separate argmin / compare /
  reduce XLA ops the engines used to issue; ``fused`` = one
  ``fused_select`` / ``fused_check`` call; ``fused_packed`` = the
  packed-uint32-activity variants the engines actually call (no
  ``to_bool`` expansion).  Every variant runs per impl (``jnp`` and
  ``pallas``).  The shape grid includes the n=2048 regression shapes
  where PR-5's row-striped blocking made pallas 8x SLOWER than jnp.
* **engine level** — full enumeration per graph x engine x
  ``kernel_impl``: wall time and steps/sec, asserted byte-identical
  (``n_max``/``cs``) between impls.
* **segment level** — bounded rounds with a ``steps_per_call`` inner
  unroll (the multi-step compiled-segment knob — backed by the
  VMEM-resident segment kernel on the pallas path): polls, wall,
  steps/sec.
* **segment_pool level** — a B-lane worker pool driven through
  ``run_batch`` at pool sizes x ``steps_per_call``: ``pool`` = the
  multi-lane resident pool kernel (ONE launch advances every lane a
  segment), ``vmap`` = the legacy vmap-of-single-lane layout
  (``resident_lanes=0``), plus the jnp reference.  All variants are
  asserted byte-identical per lane in-run; ``--regress`` additionally
  enforces pool >= ~0.8x vmap steps/s at pool sizes >= 8 so a pool-path
  slowdown hard-fails CI.

On CPU the pallas impl runs in **interpret mode**, so parity (or worse)
is expected there — the artifact records ``backend`` and carries BOTH
impls so TPU runs slot into the same trajectory and the fused speedup
becomes visible where it is real.

``--regress BASELINE.json`` replays the comparison that would have caught
the n=2048 regression: every current op-level wall time is checked
against the committed baseline per ``(op, variant, impl, n, w)`` key.
Slowdowns beyond ``--regress-tol`` HARD-FAIL when the baseline was
recorded on the same backend; cross-backend comparisons only warn (an
interpret-mode CPU wall says nothing about a TPU wall).

  python -m benchmarks.kernels --json BENCH_8.json
  python -m benchmarks.kernels --smoke --regress BENCH_8.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bitset
from repro.core import engine_dense as ed
from repro.core.engine import get_engine
from repro.data.generators import random_bipartite
from repro.kernels.fused_check.ops import fused_check, fused_check_packed
from repro.kernels.fused_select.ops import (fused_select,
                                            fused_select_packed)
from repro.kernels.intersect_count.ops import intersect_count

_INF = np.int32(0x7FFFFFFF)


def _timed(fn, *args, repeats: int):
    """(out, best_wall_s, compile_s): first call AOT-ish timed as compile."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return out, min(walls), compile_s


# ---------------------------------------------------------------------------
# op level: one candidate branch worth of select/check work
# ---------------------------------------------------------------------------

def bench_ops(n: int, w: int, repeats: int, seed: int = 0) -> list:
    rng = np.random.default_rng(seed)
    adj = jnp.asarray(rng.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    mask = jnp.asarray(rng.integers(0, 2 ** 32, (w,), dtype=np.uint32))
    nlp = jnp.int32(int(np.unpackbits(np.asarray(mask).view(np.uint8))
                        .sum()))
    act = jnp.asarray(rng.integers(0, 2, (n,)).astype(np.int32))
    qa = jnp.asarray(rng.integers(0, 2, (n,)).astype(np.int32))
    pa = jnp.asarray(rng.integers(0, 2, (n,)).astype(np.int32))

    def select_unfused(impl):
        @jax.jit
        def f(adj, mask, act):
            c = intersect_count(adj, mask, impl=impl)
            return jnp.argmin(jnp.where(act > 0, c, _INF))
        return f

    def select_fused(impl):
        return jax.jit(lambda adj, mask, act: fused_select(
            adj, mask, act, impl=impl))

    def check_unfused(impl):
        @jax.jit
        def f(adj, mask, nlp, qa, pa):
            c = intersect_count(adj, mask, impl=impl)
            viol = jnp.any((qa > 0) & (c == nlp))
            full = (pa > 0) & (c == nlp)
            part = (pa > 0) & (c > 0) & (c < nlp)
            return viol, full, part, c > 0
        return f

    def check_fused(impl):
        return jax.jit(lambda adj, mask, nlp, qa, pa: fused_check(
            adj, mask, nlp, qa, pa, impl=impl))

    # packed-activity variants: the words the engines now keep end to end
    act_w = bitset.from_bool(act > 0)
    qa_w = bitset.from_bool(qa > 0)
    pa_w = bitset.from_bool(pa > 0)

    def select_packed(impl):
        return jax.jit(lambda adj, mask, aw: fused_select_packed(
            adj, mask, aw, impl=impl))

    def check_packed(impl):
        return jax.jit(lambda adj, mask, nlp, qw, pw: fused_check_packed(
            adj, mask, nlp, qw, pw, impl=impl))

    cases = [("select", "unfused", select_unfused, (adj, mask, act)),
             ("select", "fused", select_fused, (adj, mask, act)),
             ("select", "fused_packed", select_packed, (adj, mask, act_w)),
             ("check", "unfused", check_unfused, (adj, mask, nlp, qa, pa)),
             ("check", "fused", check_fused, (adj, mask, nlp, qa, pa)),
             ("check", "fused_packed", check_packed,
              (adj, mask, nlp, qa_w, pa_w))]
    rows = []
    for op, variant, make, args in cases:
        for impl in ("jnp", "pallas"):
            _, wall, _ = _timed(make(impl), *args, repeats=repeats)
            rows.append(dict(level="op", op=op, variant=variant, impl=impl,
                             n=n, w=w, wall_us=round(wall * 1e6, 1)))
            print(f"[kernels] op {op:6s} {variant:7s} {impl:6s} "
                  f"({n}x{w}): {wall * 1e6:9.1f} us")
    return rows


# ---------------------------------------------------------------------------
# engine level: full enumeration, steps/sec per kernel_impl
# ---------------------------------------------------------------------------

def bench_engines(graphs: list, engines: list[str], repeats: int) -> list:
    rows = []
    for g in graphs:
        for engine in engines:
            eng = get_engine(engine)
            ref = None
            for impl in ("jnp", "pallas"):
                cfg = eng.make_config(g, kernel_impl=impl)
                ctx = eng.make_context(g, cfg)
                s0 = eng.init_state(cfg, np.arange(g.n_u, dtype=np.int32))
                runner = jax.jit(lambda s, c=ctx, cf=cfg, e=eng:
                                 e.run(c, cf, s))
                out, wall, compile_s = _timed(runner, s0, repeats=repeats)
                assert bool(eng.done(out)), (g.name, engine, impl)
                key = (int(out.n_max), int(out.cs), int(out.steps))
                if ref is None:
                    ref = key
                assert key == ref, \
                    f"{g.name}/{engine}: pallas != jnp ({key} vs {ref})"
                steps = int(out.steps)
                rows.append(dict(
                    level="engine", graph=g.name, n_u=g.n_u, n_v=g.n_v,
                    engine=engine, impl=impl, steps=steps,
                    n_max=int(out.n_max), wall_s=round(wall, 4),
                    compile_s=round(compile_s, 3),
                    steps_per_s=round(steps / wall, 1)))
                print(f"[kernels] engine {g.name:16s} {engine:7s} "
                      f"{impl:6s}: {steps:6d} steps, {wall:8.4f}s "
                      f"({steps / wall:10.1f} steps/s)")
    return rows


# ---------------------------------------------------------------------------
# segment level: steps_per_call unroll over bounded rounds
# ---------------------------------------------------------------------------

def bench_segments(g, steps_per_round: int, unrolls: list[int],
                   repeats: int) -> list:
    eng = get_engine("dense")
    cfg = eng.make_config(g)
    ctx = eng.make_context(g, cfg)
    s0 = eng.init_state(cfg, np.arange(g.n_u, dtype=np.int32))
    rows, ref = [], None

    def drive(runner, s):
        polls = 0
        while not bool(eng.done(s)):
            s = runner(s)
            polls += 1
        return jax.block_until_ready(s), polls

    for unroll in unrolls:
        runner = jax.jit(lambda s, u=unroll: eng.run(
            ctx, cfg, s, max_steps=steps_per_round, unroll=u))
        drive(runner, s0)                       # compile + warm
        walls, polls = [], 0
        for _ in range(repeats):
            t0 = time.perf_counter()
            out, polls = drive(runner, s0)
            walls.append(time.perf_counter() - t0)
        wall = min(walls)
        key = (int(out.n_max), int(out.cs), int(out.steps))
        if ref is None:
            ref = key
        assert key == ref, f"unroll={unroll} diverged: {key} vs {ref}"
        steps = int(out.steps)
        rows.append(dict(
            level="segment", graph=g.name, steps_per_round=steps_per_round,
            steps_per_call=unroll, polls=polls, steps=steps,
            wall_s=round(wall, 4), steps_per_s=round(steps / wall, 1)))
        print(f"[kernels] segment {g.name:16s} spr={steps_per_round} "
              f"x{unroll:2d}/call: {polls:4d} polls, {wall:8.4f}s "
              f"({steps / wall:10.1f} steps/s)")
    return rows


# ---------------------------------------------------------------------------
# segment_pool level: multi-lane pool kernel vs vmap-of-single-lane
# ---------------------------------------------------------------------------

def _pool_state(cfg, n_u: int, lanes: int):
    """B-lane batch over disjoint root chunks (equal t_len, ragged
    n_tasks) — the distributed runner's per-device worker layout."""
    chunks = np.array_split(np.arange(n_u, dtype=np.int32), lanes)
    t_len = max(len(c) for c in chunks)
    states = []
    for c in chunks:
        t = np.full(t_len, -1, dtype=np.int32)
        t[: len(c)] = c
        states.append(ed.init_state(cfg, t)._replace(
            n_tasks=jnp.int32(len(c))))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def bench_segment_pool(g, steps_per_round: int, pools: list[int],
                       unrolls: list[int], repeats: int) -> list:
    eng = get_engine("dense")
    rows = []
    for pool in pools:
        for unroll in unrolls:
            ref = None
            for variant, impl, lanes_knob in (
                    ("pool", "pallas", "auto"),
                    ("vmap", "pallas", 0),
                    ("vmap", "jnp", 0)):
                cfg = dataclasses.replace(
                    eng.make_config(g, kernel_impl=impl),
                    resident_lanes=lanes_knob)
                if variant == "pool":
                    assert ed.pool_lanes(cfg, pool) == pool, \
                        f"pool gate rejected B={pool} on {g.name}"
                ctx = eng.make_context(g, cfg)
                s0 = _pool_state(cfg, g.n_u, pool)
                runner = jax.jit(lambda s, c=ctx, cf=cfg, u=unroll:
                                 ed.run_batch(c, cf, s,
                                              max_steps=steps_per_round,
                                              unroll=u))

                def drive(s):
                    polls = 0
                    while not bool(jnp.all(ed._done(s))):
                        s = runner(s)
                        polls += 1
                    return jax.block_until_ready(s), polls

                drive(s0)                   # compile + warm
                walls, polls = [], 0
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    out, polls = drive(s0)
                    walls.append(time.perf_counter() - t0)
                wall = min(walls)
                key = (np.asarray(out.n_max).tolist(),
                       np.asarray(out.cs).tolist(),
                       np.asarray(out.steps).tolist())
                if ref is None:
                    ref = key
                assert key == ref, (f"pool={pool} x{unroll} "
                                    f"{variant}/{impl} diverged per lane")
                steps = int(np.asarray(out.steps, dtype=np.int64).sum())
                rows.append(dict(
                    level="segment_pool", graph=g.name, pool=pool,
                    steps_per_round=steps_per_round,
                    steps_per_call=unroll, variant=variant, impl=impl,
                    polls=polls, steps=steps, wall_s=round(wall, 4),
                    steps_per_s=round(steps / wall, 1)))
                print(f"[kernels] segment_pool {g.name:12s} B={pool:2d} "
                      f"x{unroll:2d}/call {variant:4s}/{impl:6s}: "
                      f"{polls:4d} polls, {wall:8.4f}s "
                      f"({steps / wall:10.1f} steps/s)")
    return rows


def pool_parity_check(rows: list, min_pool: int = 8,
                      floor: float = 0.8) -> int:
    """The acceptance gate for the multi-lane pool kernel: at pool sizes
    >= ``min_pool`` the one-launch pool path must hold >= ``floor`` x
    the vmap-of-single-lane steps/s ON THE SAME RUN (both pallas, same
    backend, so the comparison is launch-overhead apples to apples).
    Returns the number of failures."""
    by_key = {}
    for r in rows:
        if r.get("level") == "segment_pool" and r["impl"] == "pallas":
            by_key[(r["pool"], r["steps_per_call"], r["variant"])] = \
                r["steps_per_s"]
    failures = 0
    for (pool, spc, variant), v in sorted(by_key.items()):
        if variant != "pool" or pool < min_pool:
            continue
        ref = by_key.get((pool, spc, "vmap"))
        if not ref:
            continue
        ratio = v / ref
        bad = ratio < floor
        print(f"[kernels] pool parity B={pool:2d} x{spc:2d}/call: "
              f"pool {v:.1f} vs vmap {ref:.1f} steps/s "
              f"({ratio:.2f}x){'  FAIL' if bad else ''}")
        failures += bad
    return failures


# ---------------------------------------------------------------------------
# --regress: wall-time comparison against a committed baseline artifact
# ---------------------------------------------------------------------------

def regress_check(rows: list, backend: str, baseline_path: str,
                  tol: float) -> int:
    """Compare current op-level wall times against ``baseline_path`` per
    ``(op, variant, impl, n, w)`` key, and segment_pool-level wall times
    per ``(pool, steps_per_call, variant, impl)``.  Returns the number
    of HARD failures: slowdowns beyond ``tol`` x with both runs on the
    same backend.  Cross-backend slowdowns (or keys missing on either
    side) only warn — the artifact schema carries both impls precisely
    so runs from different platforms can coexist in one trajectory."""
    with open(baseline_path) as f:
        base = json.load(f)
    base_backend = base.get("summary", {}).get("backend")
    same = base_backend == backend
    base_walls = {
        (r["op"], r["variant"], r["impl"], r["n"], r["w"]): r["wall_us"]
        for r in base.get("rows", []) if r.get("level") == "op"}
    base_pool = {
        (r["pool"], r["steps_per_call"], r["variant"], r["impl"]):
            r["wall_s"]
        for r in base.get("rows", [])
        if r.get("level") == "segment_pool"}
    failures = compared = 0
    # the full per-key ratio table is printed on PASS too — a silent
    # "0 failures" hides drift creeping toward the tolerance
    print(f"[kernels] regress table (tol {tol:.2f}x):")
    print(f"  {'op':<14} {'variant':<10} {'impl':<7} {'n':>5} {'w':>3} "
          f"{'base_us':>9} {'now_us':>9} {'ratio':>6}")
    for r in rows:
        if r.get("level") != "op":
            continue
        key = (r["op"], r["variant"], r["impl"], r["n"], r["w"])
        ref = base_walls.get(key)
        if ref is None or ref <= 0:
            continue
        compared += 1
        ratio = r["wall_us"] / ref
        bad = ratio > tol
        tag = ("" if not bad
               else "  FAIL" if same else "  warn (cross-backend)")
        print(f"  {key[0]:<14} {key[1]:<10} {key[2]:<7} {key[3]:>5} "
              f"{key[4]:>3} {ref:>9.1f} {r['wall_us']:>9.1f} "
              f"{ratio:>5.2f}x{tag}")
        failures += bad and same
    for r in rows:
        if r.get("level") != "segment_pool":
            continue
        key = (r["pool"], r["steps_per_call"], r["variant"], r["impl"])
        ref = base_pool.get(key)
        if ref is None or ref <= 0:
            continue
        compared += 1
        ratio = r["wall_s"] / ref
        bad = ratio > tol
        tag = ("" if not bad
               else "  FAIL" if same else "  warn (cross-backend)")
        print(f"  {'segment_pool':<14} {key[2]:<10} {key[3]:<7} "
              f"B={key[0]:>3} {key[1]:>3} {ref * 1e3:>9.1f} "
              f"{r['wall_s'] * 1e3:>9.1f} {ratio:>5.2f}x{tag}")
        failures += bad and same
    print(f"[kernels] regress vs {baseline_path}: {compared} keys "
          f"compared (baseline backend={base_backend}, current={backend}"
          f"{', same platform' if same else ', cross-platform'}), "
          f"{failures} hard failure(s)")
    return failures


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 repeat (CI-sized)")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--steps-per-round", type=int, default=64)
    ap.add_argument("--json", type=str, default=None, metavar="OUT",
                    help="write the artifact (e.g. BENCH_6.json)")
    ap.add_argument("--regress", type=str, default=None, metavar="BASE",
                    help="compare op-level walls against this committed "
                         "artifact; exit 1 on same-backend slowdowns "
                         "beyond --regress-tol")
    ap.add_argument("--regress-tol", type=float, default=5.0,
                    help="max allowed wall-time ratio vs baseline "
                         "(generous: interpret-mode walls at the "
                         "50-300us scale swing several-fold run to "
                         "run, but the blocking-regression class this "
                         "gate exists for is 7-8x)")
    args = ap.parse_args()
    # min-of-5 even for smoke: --regress compares wall times, and small
    # sample counts let one bad scheduling window through the min
    repeats = args.repeats or 5

    if args.smoke:
        op_shapes = [(64, 8), (2048, 64)]
        graphs = [random_bipartite(10, 18, p=0.3, seed=0, name="rand-10x18")]
    else:
        # (64, 8) keeps the smoke grid a subset so CI's --regress always
        # finds its keys; the (2048, *) rows pin the regression shapes
        op_shapes = [(64, 8), (512, 64), (2048, 64), (2048, 128),
                     (2048, 256)]
        graphs = [
            random_bipartite(16, 32, p=0.3, seed=0, name="rand-16x32"),
            random_bipartite(24, 48, p=0.2, seed=1, name="rand-24x48"),
            random_bipartite(32, 64, p=0.15, seed=2, name="rand-32x64"),
        ]

    rows = []
    for n, w in op_shapes:
        rows += bench_ops(n, w, repeats)
    engine_rows = bench_engines(graphs, ["dense", "compact"], repeats)
    rows += engine_rows
    rows += bench_segments(graphs[0], args.steps_per_round,
                           [1, 4] if args.smoke else [1, 4, 16], repeats)
    # smoke keeps the pool grid a subset of the full grid — and the SAME
    # graph — so CI's --regress always finds its segment_pool keys in
    # the baseline and the wall ratios compare like with like
    pool_graph = random_bipartite(16, 32, p=0.3, seed=0, name="rand-16x32")
    rows += bench_segment_pool(
        pool_graph, args.steps_per_round,
        pools=[1, 8] if args.smoke else [1, 4, 8, 16],
        unrolls=[1, 16] if args.smoke else [1, 4, 16],
        repeats=repeats)

    # headline: per-impl engine-level steps/sec (geomean over graphs x
    # engines) + the fused:unfused ratio — the number a TPU run moves
    per_impl = {}
    for impl in ("jnp", "pallas"):
        v = [r["steps_per_s"] for r in engine_rows if r["impl"] == impl]
        per_impl[impl] = round(float(np.exp(np.mean(np.log(v)))), 1)
    summary = dict(
        backend=jax.default_backend(),
        interpret_mode=jax.default_backend() != "tpu",
        engine_steps_per_s=per_impl,
        fused_speedup=round(per_impl["pallas"] / per_impl["jnp"], 3),
        repeats=repeats,
    )
    print(f"[kernels] engine steps/s geomean: {per_impl} "
          f"(fused/unfused = {summary['fused_speedup']}x, "
          f"backend={summary['backend']}"
          f"{', interpret' if summary['interpret_mode'] else ''})")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dict(benchmark="kernels", summary=summary, rows=rows),
                      f, indent=2, sort_keys=True)
        print(f"[kernels] wrote {args.json}")
    if args.regress:
        bad = regress_check(rows, summary["backend"], args.regress,
                            args.regress_tol)
        bad += pool_parity_check(rows)
        return 1 if bad else 0
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
