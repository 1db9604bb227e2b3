"""``chip_smoke.py``'s phases at ``dataset_suite("test")`` scale on the CPU
(Pallas kernels in interpret mode), so the script cannot rot between chip
runs.  Only its ``main()`` checks for a TPU, and refuses the CPU."""
import importlib.util
import os

import pytest

from repro.data import dataset_suite, random_bipartite


@pytest.fixture(scope="module")
def smoke():
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_big():
    return random_bipartite(48, 80, 0.1, seed=7, name="cumbe-tiny")


def test_phase_a_stream(smoke):
    graphs = list(dataset_suite("test").values())[:3]
    out = smoke.phase_a(graphs, graphs[:1], kernel_impl="pallas")
    assert set(out) == {"dense", "compact"}
    assert len(out["dense"]) == len(graphs)


def test_phase_b_step_capped(smoke):
    n_max, cs, nodes, steps = smoke.phase_b(
        _tiny_big(), kernel_impl="pallas", workers=2, round_steps=8, cap=24)
    assert nodes > 0 and steps >= 24


def test_phase_mesh_one_device(smoke):
    graphs = list(dataset_suite("test").values())[:2]
    out = smoke.phase_mesh(graphs, [(graphs[0], None), (_tiny_big(), 24)],
                           n_chips=1, kernel_impl="pallas", round_steps=8)
    assert out[graphs[0].name][0] == "done"
    assert out["cumbe-tiny"][0] == "step_capped"


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""
