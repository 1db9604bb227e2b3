"""A cell with a skewed big graph served beside small ones, added as new
files and manifest entries alone: a configuration that routes big graphs
to the work-stealing big-graph lane, a mix with a ``powerlaw`` kind at a
published big shape capped at one in flight and checked whole, and a cell.
Written at full size, cut by ``tiny_copy``, and run on the CPU."""
import json
import os
import re
import shutil

import pytest

from _tiny import BIG_U, ROOT, SHAPES, tiny_copy

from bench import run, traffic

BIG = dict(label="marvel", source="KONECT marvel: 6486 x 12942, 96662 edges",
           family="powerlaw", n_u=6486, n_v=12942, edges=96662, a_u=0.6,
           a_v=0.6, share=1)


def _write(path, d):
    with open(path, "w") as f:
        json.dump(d, f)


def _full_size_source(dst):
    """This repository's benchmark plus the new cell's files, at full
    size."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(dst, "bench")
    with open(os.path.join(b, "configs", "svc-dense.json")) as f:
        conf = json.load(f)
    conf["options"]["big_graph_threshold"] = 4096
    _write(os.path.join(b, "configs", "svc-big.json"), conf)
    mix = traffic.load_mix("konect-small-closed", b)
    mix["graphs"].append(BIG)
    mix["loop"]["max_in_flight"] = {"marvel": 1}
    mix["check"]["every"] = ["marvel"]
    _write(os.path.join(b, "traffic", "big-beside-small.json"), mix)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(dict(manifest["configs"][0], name="svc-big",
                                    file="bench/configs/svc-big.json"))
    manifest["workloads"].append(dict(
        name="dense.big-beside-small", config="svc-big",
        traffic="big-beside-small", chips=1, why="test"))
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("dense.big-beside-small")
    _write(os.path.join(dst, "BENCHMARK.json"), manifest)
    return dst


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    src = _full_size_source(str(tmp_path_factory.mktemp("full")))
    return tiny_copy(str(tmp_path_factory.mktemp("tiny")), root=src)


def test_tiny_copy_keeps_every_kind_on_its_route(tiny):
    """A fourth kind survives the cut; the big one stays at the tiny
    threshold, the small ones below it; configurations without a
    threshold and mixes without the new keys are cut as before."""
    b = os.path.join(tiny, "bench")
    mix = traffic.load_mix("big-beside-small", b)
    assert [k["label"] for k in mix["graphs"]] == [
        "corporate-leadership", "unicodelang", "ucforum", "marvel"]
    assert [(k["n_u"], k["n_v"], k["edges"]) for k in mix["graphs"][:3]] \
        == SHAPES
    big = mix["graphs"][3]
    assert big["n_u"] >= BIG_U > max(nu for nu, _, _ in SHAPES)
    assert (big["family"], big["a_u"]) == ("powerlaw", 0.6)
    assert mix["loop"] == dict(outstanding=4, max_in_flight={"marvel": 1})
    assert mix["check"] == dict(sample=6, every=["marvel"])
    with open(os.path.join(b, "configs", "svc-big.json")) as f:
        assert json.load(f)["options"]["big_graph_threshold"] == BIG_U
    with open(os.path.join(b, "configs", "svc-dense.json")) as f:
        assert "big_graph_threshold" not in json.load(f)["options"]
    old = traffic.load_mix("konect-small-closed", b)
    assert old["check"] == {"sample": 6} and old["loop"] == {"outstanding": 4}


def test_big_beside_small_cell_runs_correct(tiny, monkeypatch, capfd):
    """Correct on the CPU, no compile in the window, every big answer
    compared with the reference."""
    picked = []
    sample = run.check_sample

    def record(done, check_cfg, seed):
        pick = sample(done, check_cfg, seed)
        picked.append(([traffic.label(a["graph"]) for a in done], pick))
        return pick
    monkeypatch.setattr(run, "check_sample", record)
    result, checks = run.run_cell("dense.big-beside-small", 2 ** 33 + 29,
                                  4.0, False, root=tiny, need_chip=False,
                                  grace_s=60.0)
    assert result["correct"] is True, result["check"]
    assert set(result["metrics"]) == {"setup_s", "stream_rps"}
    (labels, pick), = picked
    big = [i for i, k in enumerate(labels) if k == "marvel"]
    assert big and set(big) <= set(pick)
    assert checks["checked"][0] == len(pick) == len(big) + 6
    err = capfd.readouterr().err
    misses, lowerings = re.search(
        r"executable-cache misses=(\d+) jax lowerings=(\d+)", err).groups()
    assert (misses, lowerings) == ("0", "0"), err
