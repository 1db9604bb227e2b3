"""Training substrate: optimizer math, accumulation invariance, loss
descent, gradient compression error feedback."""
from __future__ import annotations

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.models import model as M
from repro.models.layers import init_params
from repro.training.compress import (_dequantize, _quantize,
                                     init_error_state)
from repro.training.optimizer import (adamw, apply_updates,
                                      clip_by_global_norm, cosine_schedule,
                                      global_norm)
from repro.training.step import loss_fn, make_train_step

# seed-era LM infrastructure suite: quarantined from the tier-1
# fast lane (pyproject addopts deselects seed_lm); CI's full-suite
# leg still runs it
pytestmark = pytest.mark.seed_lm


def _setup(arch="qwen3-1.7b", seed=0):
    cfg = get_smoke(arch)
    params = init_params(M.param_specs(cfg), jax.random.key(seed))
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 32), dtype=np.int32))
    return cfg, params, dict(tokens=toks, labels=toks)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100, floor=0.1)
    assert float(lr(jnp.int32(0))) == 0.0
    assert abs(float(lr(jnp.int32(10))) - 1e-3) < 1e-9
    assert float(lr(jnp.int32(100))) == pytest.approx(1e-4, rel=1e-3)
    assert float(lr(jnp.int32(55))) < 1e-3


def test_clip_by_global_norm():
    tree = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(10.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_adamw_direction_and_decay():
    opt = adamw(peak_lr=1e-2, warmup=0, total_steps=10, weight_decay=0.0,
                max_grad_norm=1e9)
    params = {"w": jnp.ones((4, 4))}
    g = {"w": jnp.ones((4, 4))}
    st = opt.init(params)
    upd, st, _ = opt.update(g, st, params)
    # positive gradient -> negative update
    assert np.all(np.asarray(upd["w"]) < 0)


def test_loss_decreases():
    cfg, params, batch = _setup()
    opt = adamw(peak_lr=3e-3, warmup=2, total_steps=60)
    step = jax.jit(make_train_step(cfg, opt))
    st = opt.init(params)
    first = None
    for i in range(30):
        params, st, m = step(params, st, batch)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first * 0.7, (first, float(m["loss"]))


def _grad_probe_opt():
    """Stub optimizer whose 'update' IS the averaged gradient — so
    params_out - params_in exposes the step's accumulated grads exactly
    (comparing post-Adam params is ill-posed: m/sqrt(v) ~ sign(g) flips
    on 1e-7 gradient noise)."""
    from repro.training.optimizer import Optimizer

    def init(params):
        return jnp.int32(0)

    def update(g, st, params):
        return g, st, dict(lr=jnp.float32(0), grad_norm=global_norm(g))

    return Optimizer(init=init, update=update)


def test_grad_accum_invariance():
    """accum=4 on a batch == accum=1 on the same batch (same grads),
    fp32 compute, compared at the gradient level."""
    import dataclasses
    cfg, params, batch = _setup()
    cfg = dataclasses.replace(cfg, dtype="float32")
    opt = _grad_probe_opt()
    s1 = jax.jit(make_train_step(cfg, opt, accum=1))
    s4 = jax.jit(make_train_step(cfg, opt, accum=4))
    p1, _, m1 = s1(dict(params), opt.init(params), batch)
    p4, _, m4 = s4(dict(params), opt.init(params), batch)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-5)
    for k in params:
        g1 = np.asarray(p1[k]) - np.asarray(params[k])
        g4 = np.asarray(p4[k]) - np.asarray(params[k])
        np.testing.assert_allclose(g1, g4, rtol=1e-3, atol=1e-5)


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256,)) * 3.0, jnp.float32)
    codes, scale = _quantize(x)
    err = np.abs(np.asarray(_dequantize(codes, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_compression_error_feedback_converges():
    """With error feedback, the *running sum* of compressed psums tracks
    the running sum of exact gradients (EF property), single participant."""
    rng = np.random.default_rng(1)
    gs = [jnp.asarray(rng.normal(size=(64,)), jnp.float32)
          for _ in range(50)]

    import jax
    from jax.sharding import PartitionSpec as P
    from repro.training.compress import quantized_psum

    def run_once(g, e):
        return quantized_psum({"g": g}, "x", {"g": e})

    run = jax.jit(jax.shard_map(
        run_once, mesh=jax.make_mesh((1,), ("x",)),
        in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False))

    e = jnp.zeros((64,))
    acc_c = np.zeros(64)
    acc_t = np.zeros(64)
    for g in gs:
        red, new_e = run(g, e)
        e = new_e["g"]
        acc_c += np.asarray(red["g"])
        acc_t += np.asarray(g)
    # residual is bounded by one quantization step, not O(n_steps)
    assert np.abs(acc_c - acc_t).max() < 0.05 * np.abs(acc_t).max() + 0.2


def test_vlm_loss_masks_patch_positions():
    cfg, params, _ = _setup("internvl2-2b")
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16), dtype=np.int32))
    pe = jnp.asarray(rng.normal(size=(2, cfg.patch_tokens, cfg.d_model)),
                     jnp.bfloat16) * 0
    loss, metrics = loss_fn(cfg, params,
                            dict(tokens=toks, labels=toks, patch_emb=pe))
    # loss over exactly the text positions
    assert int(metrics["tokens"]) == 2 * 16
    assert np.isfinite(float(loss))
