"""The roofline readers of the compact engine's gathered kernels
(``fused_select_roofline``, ``fused_check_roofline``; ``bench/roofline.py``),
checked by hand on a synthetic trace reduction: no reading without a trace
or without the program's counters, an error where the trace has device
time but no operation is named for the kernel, and otherwise the share
computed by hand."""
import pytest

from _tiny import ROOT

from bench import roofline
from bench.run import load_reader
from bench.trace import Reduction

V5E_HBM = 819e9
OPS = {
    "%fused_select.3 = (s32[8]{0}, s32[8]{0}) custom-call(u32[8,1024,32] "
    "%gather.1)": 2e-3,
    "%fused_select.3.clone = (s32[8]{0}, s32[8]{0}) custom-call()": 1e-3,
    "%fused_check.7 = (s32[8]{0}) custom-call(u32[8,2048,32] %gather.2)":
        5e-3,
    # names a kernel only among its operands: not the kernel's time
    "%fusion.12 = s32[8]{0} fusion(s32[8]{0} %fused_select.3, "
    "s32[8]{0} %fused_check.7)": 7e-3,
    "%while.4 = (u32[8,1024,32]) while(%tuple.1)": 20e-3,
}
BEFORE = dict(gathered_select_words=1000, gathered_check_words=4000)
AFTER = dict(gathered_select_words=1000 + 3_000_000,
             gathered_check_words=4000 + 8_000_000)
CASES = [("fused_select_roofline.compact", 3e-3, 3_000_000),
         ("fused_check_roofline.compact", 5e-3, 8_000_000)]


def _trace(ops, busy_s=0.03):
    return Reduction(window_s=0.05, busy_s=busy_s, op_s=dict(ops),
                     idle_s={}, n_devices=1)


def _run(trace, before=BEFORE, after=AFTER):
    return dict(trace=trace, stats_before=before, stats_after=after)


@pytest.fixture(autouse=True)
def v5e(monkeypatch):
    monkeypatch.setattr(roofline, "device_kind", lambda: "TPU v5 lite")


@pytest.mark.parametrize("name, kernel_s, words", CASES)
def test_share_by_hand(name, kernel_s, words):
    read = load_reader(ROOT, "layers", name)
    assert read(_run(_trace(OPS))) == pytest.approx(
        100 * 4 * words / (kernel_s * V5E_HBM))


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_none_without_trace_or_counters(name):
    """No trace, or a program whose ``stats()`` lacks the counters (the
    parent of the counters): no reading, and no error."""
    read = load_reader(ROOT, "layers", name)
    assert read(_run(None)) is None
    bare = dict(busy_steps=0)
    assert read(_run(_trace(OPS), bare, bare)) is None
    assert read(_run(_trace({}), bare, bare)) is None


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_raises_when_no_op_is_named_for_the_kernel(name):
    read = load_reader(ROOT, "layers", name)
    others = {k: v for k, v in OPS.items()
              if k.startswith(("%fusion", "%while"))}
    with pytest.raises(ValueError, match="no operation is named"):
        read(_run(_trace(others)))
    assert read(_run(_trace({}, busy_s=0.0))) is None


def test_peak_is_looked_up_by_device_kind(monkeypatch):
    monkeypatch.setattr(roofline, "device_kind", lambda: "TPU v99")
    with pytest.raises(KeyError):
        roofline.share(_run(_trace(OPS)), "fused_check",
                       "gathered_check_words")
