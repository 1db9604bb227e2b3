"""The benchmark harness on the CPU at test size: every cell's run
through its arguments, the shape of the result line, the TPU-only guard,
the peaks table, and that a new configuration, traffic mix or metric is
found by its name alone."""
import json
import os

import pytest

from _tiny import CELLS, ROOT, tiny_copy

from bench import peaks, run, traffic

KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct(tiny, workload):
    result, checks = run.run_cell(workload, 2 ** 33 + 17, 4.0, False,
                                  root=tiny, need_chip=False, grace_s=20.0)
    assert list(result) == KEYS            # the check comes last
    assert result["correct"] is True, result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    manifest = json.load(open(os.path.join(tiny, "BENCHMARK.json")))
    want = {m["name"] for m in run.cell_metrics(manifest, workload, False)}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert checks["wrong_count"][0] == 0 and checks["checked"][0] >= 1


def test_same_seed_same_requests():
    """The cell's own mix: a seed gives the same graphs again; another
    seed gives other graphs of the same published shapes and edge counts,
    every vertex covered."""
    mix = traffic.load_mix("konect-small-closed")
    a = traffic.generate(mix, 2 ** 40 + 3, 30)
    b = traffic.generate(mix, 2 ** 40 + 3, 30)
    c = traffic.generate(mix, 2 ** 40 + 4, 30)
    assert [g.name for g in a] == [g.name for g in b]
    assert all((x.rows == y.rows).all() for x, y in zip(a, b))
    published = {(k["n_u"], k["n_v"], k["edges"]) for k in mix["graphs"]}
    for g in a + c:
        assert (g.n_u, g.n_v, int(g.rows.sum())) in published
        assert g.rows.any(axis=0).all() and g.rows.any(axis=1).all()
    assert sorted((g.n_u, g.n_v) for g in a) \
        == sorted((g.n_u, g.n_v) for g in c)
    assert any((x.rows.shape != y.rows.shape) or (x.rows != y.rows).any()
               for x, y in zip(a, c))


@pytest.mark.parametrize("seed", [5, 2 ** 33 + 1])
def test_every_prefix_keeps_the_shares(seed):
    """Any prefix (what one window serves) holds each graph kind within
    one round of its share; the seed changes only the order in a round."""
    mix = traffic.load_mix("konect-small-closed")
    mix["graphs"][0] = dict(mix["graphs"][0], share=2)
    labels = [g.name.split("-", 1)[1] for g in
              traffic.generate(mix, seed, 41)]
    shares = {k["label"]: k.get("share", 1) for k in mix["graphs"]}
    per_round = sum(shares.values())
    for n in range(1, len(labels) + 1):
        rounds = n / per_round
        for label, share in shares.items():
            assert abs(labels[:n].count(label) - share * rounds) <= share


def test_main_refuses_cpu(capsys):
    assert run.main(["--workload", "dense.konect-small-sat", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_peaks_table():
    p = peaks.lookup("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes"],
            p["hbm_bytes_per_s"]) == (197e12, 393e12, 16e9, 819e9)
    with pytest.raises(KeyError):
        peaks.lookup("TPU v99")


def test_new_files_found_by_name(tmp_path):
    """A configuration, a traffic mix and two metrics added as files plus
    manifest entries, with no edit to any file the benchmark has."""
    root = tiny_copy(str(tmp_path))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "svc-dense.json")) as f:
        conf = json.load(f)
    conf["options"]["max_batch"] = 1
    with open(os.path.join(b, "configs", "svc-new.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(b, "traffic", "konect-small-closed.json")) as f:
        mix = json.load(f)
    mix["graphs"] = mix["graphs"][:1]
    mix["loop"]["outstanding"] = 2
    with open(os.path.join(b, "traffic", "stream-new.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(b, "e2e", "answered.py"), "w") as f:
        f.write("def read(run):\n    return len(run['answers'])\n")
    with open(os.path.join(b, "layers", "answered_layer.py"), "w") as f:
        f.write("def read(run):\n    return 7.0\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(dict(manifest["configs"][0], name="svc-new",
                                    file="bench/configs/svc-new.json"))
    manifest["workloads"].append(dict(
        name="new.stream", config="svc-new", traffic="stream-new", chips=1,
        why="test"))
    manifest["end_to_end"].append(dict(
        name="answered", unit="req", better="higher", bound=0.01,
        source="host_clock", workloads=["new.stream"]))
    manifest["per_layer"].append(dict(
        name="answered_layer", unit="req", better="higher",
        source="program_counter", layer="scheduler", moves="answered",
        workloads=["new.stream"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    result, _ = run.run_cell("new.stream", 5, 2.0, False, root=root,
                             need_chip=False, grace_s=10.0)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "answered"}
    assert result["metrics"]["answered"]["value"] >= 1
    layer = run.cell_metrics(manifest, "new.stream", True)
    assert [m["name"] for m in layer] == ["answered_layer"]
    assert run.load_reader(root, "layers", "answered_layer")({}) == 7.0


def test_counter_readers_by_hand():
    """The per-layer readers of the program's counters take deltas over
    the window, and every per-layer metric of the manifest finds its
    reader (one file per quantity, whatever cell class the name ends in)."""
    before = dict(busy_steps=100, total_lane_steps=400)
    after = dict(busy_steps=400, total_lane_steps=1000)
    run_ = dict(stats_before=before, stats_after=after)
    for name in ("occupancy.stream", "occupancy.big"):
        occ = run.load_reader(ROOT, "layers", name)
        assert occ(run_) == pytest.approx(100 * 300 / 600)
        idle = dict(stats_before=before,
                    stats_after=dict(after, total_lane_steps=400))
        assert occ(idle) is None
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in manifest["per_layer"]:
        assert callable(run.load_reader(ROOT, "layers", m["name"]))
    for m in manifest["end_to_end"]:
        assert callable(run.load_reader(ROOT, "e2e", m["name"]))
