"""Benchmark harness: run one cell of ``BENCHMARK.json`` on the chip.

    python bench/run.py --workload dense.konect-small --seed 7 \\
        --seconds 10 --trace 0

A cell names a configuration (``bench/configs/<config>.json``: the
service's ``MBEOptions``, its guarantee, its scale) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/traffic.py``).  A run

1. refuses any platform but a TPU, and fewer chips than the cell asks;
2. builds one ``MBEClient`` and the cell's requests from ``--seed``;
3. warms every executable the window can use: for each shape bucket the
   requests reach, one pool of each lane count the scheduler can plan;
4. offers the mix's load through ``MBEClient.submit`` / ``client.poll``
   for ``--seconds``, a closed loop of a fixed number of outstanding
   requests
   (with ``--trace 1`` under the profiler, each call in a host span);
5. reads the metrics the manifest lists for the cell: end-to-end ones
   (``bench/e2e/<name>.py``) with ``--trace 0``, per-layer ones
   (``bench/layers/<name>.py``) with ``--trace 1``;
6. frees the program, checks a seeded sample of the answers against the
   plain reference (``bench/check.py``), and prints the result as one
   JSON line, last on stdout, with each compared number and its limit
   last on stderr too.

Set-up (``setup_s``) runs from the start of this file to the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
    sys.path[0] = ROOT          # run as a script: import bench.* from ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from bench import check, peaks, traffic  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


# -- the manifest and the files it names ------------------------------------

def load_cell(workload: str, root: str = ROOT) -> tuple[dict, dict, dict,
                                                        dict]:
    """``(manifest, cell, config, mix)`` for a workload name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    mix = traffic.load_mix(cell["traffic"], os.path.join(root, "bench"))
    return manifest, cell, config, mix


def cell_metrics(manifest: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics this cell reports: its per-layer ones when traced,
    else its end-to-end ones."""
    group = manifest["per_layer" if traced else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_reader(root: str, kind: str, name: str):
    """The ``read(run) -> float | None`` of ``bench/<kind>/<name>.py``,
    or, where there is none, of the reader of the quantity the name
    splits from: ``idle_share.stream`` and ``idle_share.big`` are both
    read by ``bench/layers/idle_share.py``."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(root, "bench", kind, f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- requests ---------------------------------------------------------------

def _words(bits: np.ndarray, n: int) -> np.ndarray:
    packed = np.packbits(bits, axis=1, bitorder="little")
    out = np.zeros((bits.shape[0], 4 * ((n + 31) // 32)), np.uint8)
    out[:, : packed.shape[1]] = packed
    return out.view("<u4").astype(np.uint32)


def to_program(g: traffic.Graph):
    """The program's graph type, packed straight from the rows."""
    from repro.core.graph import BipartiteGraph
    us, vs = np.nonzero(g.rows)
    return BipartiteGraph(
        n_u=g.n_u, n_v=g.n_v, adj_u=_words(g.rows, g.n_v),
        adj_v=_words(g.rows.T, g.n_u),
        edges=np.stack([us, vs], axis=1).astype(np.int64), name=g.name)


def lane_counts(options) -> list[int]:
    """Every pool width the scheduler can plan: powers of two up to the
    lane cap (``BucketPolicy.lane_cap``)."""
    cap = options.bucket_policy().lane_cap
    return [1 << k for k in range(cap.bit_length())]


def warm(client, requests: list, spans) -> int:
    """Serve near-empty graphs of a member's shape through every bucket
    the requests reach, so that the window compiles nothing; returns how
    many warm requests ran.

    Lane counts and refills: for each pool width ``B`` the scheduler can
    plan, one slow graph (a crown, ``traffic.slow_graph``) holds a lane of
    a ``B``-lane pool while ``k = 1 .. B-1`` fast graphs are refilled
    beside it, then ``B`` more make the pool grow; this reaches the
    executables of every ``(bucket, B)`` and the lane-surgery shapes of
    every refill count."""
    from repro.serving.buckets import plan_bucket
    policy = client.options.bucket_policy()
    shapes = {}
    for g in requests:
        shapes.setdefault(plan_bucket(g, policy), (g.n_u, g.n_v))
    widths = lane_counts(client.options)
    n = [0]

    def serve(graphs):
        futs = [client.submit(to_program(g)) for g in graphs]
        n[0] += len(futs)
        while not all(f.done() for f in futs):
            with spans("bench.warm"):
                client.poll()
        return futs

    for b in sorted(shapes, key=lambda b: (b.n_u, b.n_v)):
        nu, nv = shapes[b]

        def fast(k):
            return [traffic.warm_graph(nu, nv, f"warm-{nu}x{nv}-{i}")
                    for i in range(k)]
        serve(fast(1))
        for lanes in widths[1:]:
            slow = client.submit(to_program(traffic.slow_graph(nu, nv)))
            n[0] += 1
            serve(fast(lanes - 1))
            for k in range(1, lanes):
                serve(fast(k))
            if lanes < widths[-1]:
                serve(fast(lanes))
            while not slow.done():
                with spans("bench.warm"):
                    client.poll()
    return n[0]


# -- the window -------------------------------------------------------------

def serve_window(client, requests: list, loop: dict, seconds: float,
                 grace_s: float, spans) -> dict:
    """A closed loop: keep ``loop['outstanding']`` requests in flight for
    ``seconds``, each timed from its submission, then wait up to
    ``grace_s`` for the requests still out.  The window's metrics read
    the requests answered before it closed; requests not answered by the
    end of the grace are ``missing``."""
    out: dict[int, tuple] = {}      # rid -> (future, t_submit, request)
    done: list[dict] = []
    nxt = 0
    t0 = time.perf_counter()
    stop = t0 + seconds

    def collect(batch, t):
        for rid in batch:
            item = out.pop(rid, None)
            if item is None:
                continue
            fut, ts, g = item
            with spans("bench.result"):
                res = fut.result()
            done.append(dict(latency_s=t - ts, status=res.status,
                             n_max=res.n_max, cs=res.cs, graph=g))

    with spans(trace_mod.WINDOW):
        while time.perf_counter() < stop:
            while len(out) < loop["outstanding"]:
                g, p = requests[nxt % len(requests)]
                nxt += 1
                with spans("bench.submit"):
                    fut = client.submit(p)
                out[fut.rid] = (fut, time.perf_counter(), g)
            with spans("bench.poll"):
                batch = client.poll()
            collect(batch, time.perf_counter())
    t1 = time.perf_counter()
    stats = client.stats()
    in_window = len(done)
    while out and time.perf_counter() < t1 + grace_s:
        collect(client.poll(), time.perf_counter())
    return dict(window_s=t1 - t0, attempted=nxt,
                wrapped=max(nxt - len(requests), 0), answers=done,
                measured=done[:in_window], missing=len(out),
                stats_after=stats)


# -- one run ----------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: str = ROOT, need_chip: bool = True, grace_s: float = 60.0,
             keep_trace: str | None = None) -> tuple[dict, dict]:
    """One run; returns ``(result line, checks)``.  ``need_chip=False``
    skips the look for a TPU and the persistent compile cache (the CPU
    tests drive the rest of a run that way)."""
    manifest, cell, config, mix = load_cell(workload, root)
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if need_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"needs a TPU, found platform "
                         f"{devices[0].platform!r}")
        if len(devices) < cell["chips"]:
            raise NoChip(f"cell {workload} needs {cell['chips']} chips, "
                         f"found {len(devices)}")
        peaks.lookup(kind)
    sys.path.insert(0, os.path.join(root, "src"))
    from repro import MBEClient, MBEOptions
    if need_chip:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    lowered: list[str] = []

    def on_event(event, _duration, **kw):
        if event == COMPILE_EVENT:
            lowered.append(str(kw.get("fun_name", "?")))
    jax.monitoring.register_event_duration_secs_listener(on_event)

    spans = jax.profiler.TraceAnnotation if traced \
        else (lambda _name: contextlib.nullcontext())
    requests = [(g, to_program(g))
                for g in traffic.generate(mix, seed, mix["requests"])]
    client = MBEClient(MBEOptions(**config["options"]))
    n_warm = warm(client, [p for _, p in requests], spans)
    stats_before = client.stats()
    lowered_before = len(lowered)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans only, no Python calls
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        t_window = time.perf_counter()
        win = serve_window(client, requests, mix["loop"], seconds, grace_s,
                           spans)
        if traced:
            jax.profiler.stop_trace()
        reduction = None
        if traced:
            if keep_trace:
                shutil.copytree(log_dir, keep_trace, dirs_exist_ok=True)
            reduction = trace_mod.reduce_file(trace_mod.find_xplane(log_dir))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    compiles = win["stats_after"]["misses"] - stats_before["misses"]
    lowered_in_window = lowered[lowered_before:]
    mem = [d.memory_stats() or {} for d in devices[: cell["chips"]]]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    print(f"bench: {workload} seed={seed} warm_requests={n_warm} "
          f"setup_s={t_window - T_START:.3f} window_s={win['window_s']:.3f} "
          f"attempted={win['attempted']} measured={len(win['measured'])} "
          f"wrapped={win['wrapped']} missing={win['missing']}",
          file=sys.stderr)
    print(f"bench: compiles in window: executable-cache misses={compiles} "
          f"jax lowerings={len(lowered_in_window)} "
          f"{sorted(set(lowered_in_window))[:12]}", file=sys.stderr)

    run = dict(setup_s=t_window - T_START, window_s=win["window_s"],
               answers=win["measured"], stats_before=stats_before,
               stats_after=win["stats_after"], memory_peak_bytes=peak,
               trace=reduction)
    metrics = {}
    for m in cell_metrics(manifest, workload, traced):
        kind_dir = "layers" if traced else "e2e"
        value = load_reader(root, kind_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    answers = win["answers"]
    not_done = sum(a["status"] != "done" for a in answers)
    done = [a for a in answers if a["status"] == "done"]
    n_sample = min(mix["check"]["sample"], len(done))
    pick = sorted(np.random.default_rng(seed).choice(
        len(done), n_sample, replace=False)) if n_sample else []
    sample = [(done[i]["graph"].rows, done[i]["n_max"], done[i]["cs"])
              for i in pick]
    attempted, missing = win["attempted"], win["missing"]
    failed = not_done + missing
    # the reference runs after the window, with the program's state freed
    del client, requests, answers, done, win, run
    gc.collect()
    t_ref = time.perf_counter()
    checks = check.compare(sample, missing=missing, not_done=not_done,
                           workers=len(os.sched_getaffinity(0))
                           if need_chip else 1)
    print(f"bench: reference on {len(sample)} answers took "
          f"{time.perf_counter() - t_ref:.3f}s", file=sys.stderr)

    device = dict(platform=devices[0].platform, kind=kind,
                  count=len(devices), memory_peak_bytes=peak)
    result = dict(correct=check.passes(checks), attempted=attempted,
                  failed=failed, metrics=metrics, device=device)
    if reduction is not None:
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        result["breakdown"] = dict(
            device_ops=trace_mod.top(reduction.op_s),
            idle_gaps=trace_mod.top(reduction.idle_s))
    result["check"] = check.as_json(checks)
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profiler trace to this directory")
    args = ap.parse_args(argv)
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace),
                                  keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in check.as_lines(checks):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
