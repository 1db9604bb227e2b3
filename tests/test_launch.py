"""The MBE launchers and the package they import.

Every module under ``repro`` imports on the CPU without pulling in a
language-model stack, and ``launch/serve.py`` and ``launch/mbe_run.py``
serve end to end in-process, with answers checked against the serial
oracle."""
import importlib
import pkgutil
import sys

import jax
import pytest

import repro
from repro.baselines import enumerate_mbea
from repro.data import dataset_suite

MODULES = sorted(m.name for m in pkgutil.walk_packages(repro.__path__,
                                                       "repro."))
# packages of the language-model stack this repository no longer holds
LM_PACKAGES = ("repro.models", "repro.training", "repro.datapipe",
               "repro.checkpoint", "repro.kernels.flash_attention")


@pytest.mark.parametrize("name", MODULES)
def test_module_imports_without_lm_stack(name):
    importlib.import_module(name)
    loaded = [m for m in sys.modules if m.startswith(LM_PACKAGES)]
    assert not loaded, f"importing {name} loaded {loaded}"


@pytest.fixture
def compile_cache_in(monkeypatch, tmp_path):
    """The launchers turn the persistent compile cache on: keep it in a
    temporary directory, and off again for the tests that follow."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_serve_answers_match_oracle(compile_cache_in):
    from repro.launch import serve
    out = serve.serve(["--requests", "2", "--max-batch", "2"])
    graphs = serve._request_stream("dense", 2, 0)
    assert out["requests"] == 2 and out["engine"] == "dense"
    assert out["metric"] == sum(enumerate_mbea(g, collect=False)
                                for g in graphs)
    assert out["failed"] == 0 and out["pending"] == 0
    assert out["misses"] >= 1 and out["wall_s"] > 0


def test_mbe_run_answer_matches_oracle(compile_cache_in):
    from repro.launch import mbe_run
    out = mbe_run.main(["--suite", "test", "--steps-per-round", "512"])
    g = dataset_suite("test")["powerlaw-tiny"]
    assert out["engine"] == "dense"
    assert out["n_max"] == enumerate_mbea(g, collect=False)
    assert out["rounds"] >= 1 and out["imbalance"] >= 1.0
