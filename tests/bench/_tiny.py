"""A copy of the benchmark cut to CPU test size: every graph kind at 12-14
U vertices (one shape bucket), pools of at most two lanes, short rounds,
a few requests outstanding.  Pallas kernels are not involved on the CPU
(``kernel_impl='auto'`` resolves to the ``jnp`` path there).

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


def _edit(path, fn):
    with open(path) as f:
        d = json.load(f)
    fn(d)
    with open(path, "w") as f:
        json.dump(d, f)


def tiny_copy(dst: str) -> str:
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    b = os.path.join(dst, "bench")

    def mix(m):
        m["graphs"] = [dict(g, n_u=nu, n_v=nv, edges=e)
                       for g, (nu, nv, e) in zip(m["graphs"], SHAPES)]
        m.update(requests=64, check={"sample": 6})
        m["loop"]["outstanding"] = 4

    def options(c):
        c["options"].update(max_batch=2, steps_per_call=1,
                            steps_per_round=64)

    for name in os.listdir(os.path.join(b, "traffic")):
        _edit(os.path.join(b, "traffic", name), mix)
    for name in os.listdir(os.path.join(b, "configs")):
        _edit(os.path.join(b, "configs", name), options)
    return dst
