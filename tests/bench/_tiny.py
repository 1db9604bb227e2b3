"""A copy of the benchmark cut to CPU test size: every graph kind at 12-14
U vertices (one shape bucket), pools of at most two lanes, short rounds,
a few requests outstanding.  Pallas kernels are not involved on the CPU
(``kernel_impl='auto'`` resolves to the ``jnp`` path there).

A configuration's ``big_graph_threshold`` becomes a tiny one (``BIG_U``
and up, one per distinct threshold, in order), and each kind that a
threshold routes to the big-graph lane keeps a shape at or above the tiny
threshold that stands for it, so that the same kinds take the same route
at test size as at full size.

``CELLS``: the cells of ``BENCHMARK.json``, which the tests run."""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
SHAPES = [(12, 16, 40), (13, 20, 45), (14, 18, 60)]
BIG_U = 16


def _load(path):
    with open(path) as f:
        return json.load(f)


def _edit(path, fn):
    d = _load(path)
    fn(d)
    with open(path, "w") as f:
        json.dump(d, f)


def tiny_copy(dst: str, root: str = ROOT) -> str:
    """The benchmark of ``root`` (its manifest and ``bench/``) at test size
    in ``dst``; returns ``dst``."""
    shutil.copytree(os.path.join(root, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), dst)
    b = os.path.join(dst, "bench")
    configs = [os.path.join(b, "configs", name)
               for name in os.listdir(os.path.join(b, "configs"))]
    thresholds = sorted({t for t in (
        _load(p)["options"].get("big_graph_threshold") for p in configs)
        if t is not None})

    def shrink(i, g):
        level = sum(t <= g["n_u"] for t in thresholds)
        if level:           # big under the first ``level`` thresholds
            nu = BIG_U + level - 1
            return dict(g, n_u=nu, n_v=nu + 8, edges=nu * (nu + 8) // 2)
        nu, nv, e = SHAPES[i % len(SHAPES)]
        return dict(g, n_u=nu, n_v=nv, edges=e)

    def mix(m):
        m["graphs"] = [shrink(i, g) for i, g in enumerate(m["graphs"])]
        m.update(requests=64, check=dict(m["check"], sample=6))
        m["loop"]["outstanding"] = 4

    def options(c):
        o = c["options"]
        o.update(max_batch=2, steps_per_call=1, steps_per_round=64)
        if o.get("big_graph_threshold") is not None:
            o["big_graph_threshold"] = BIG_U + thresholds.index(
                o["big_graph_threshold"])

    for name in os.listdir(os.path.join(b, "traffic")):
        _edit(os.path.join(b, "traffic", name), mix)
    for path in configs:
        _edit(path, options)
    return dst
