"""Training substrate: optimizer correctness, accumulation equivalence,
grad compression, straggler/elastic logic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.lm_data import LMDataConfig, SyntheticLMStream
from repro.training.grad_compress import (apply_error_feedback,
                                          init_error_state)
from repro.training.optimizer import (OptConfig, apply_updates,
                                      init_opt_state)
from repro.training.straggler import HostMonitor, StepTimer
from repro.training.train_loop import (init_train_state, make_train_step)


def test_adamw_reduces_quadratic_loss():
    params = {"w": jnp.asarray([5.0, -3.0])}
    cfg = OptConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)
    state = init_opt_state(params, cfg)
    for _ in range(120):
        grads = {"w": 2 * params["w"]}
        params, state, _ = apply_updates(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_adafactor_reduces_quadratic_loss():
    params = {"w": jnp.ones((4, 6)) * 3.0}
    cfg = OptConfig(lr=0.5, kind="adafactor", weight_decay=0.0,
                    warmup_steps=1)
    state = init_opt_state(params, cfg)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        params, state, _ = apply_updates(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_grad_clipping():
    params = {"w": jnp.zeros(3)}
    cfg = OptConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0, warmup_steps=1)
    state = init_opt_state(params, cfg)
    _, _, metrics = apply_updates(params, {"w": jnp.full(3, 100.0)}, state, cfg)
    assert float(metrics["grad_norm"]) > 100.0  # reported pre-clip


def test_microbatch_accumulation_matches_full_batch():
    cfg = get_config("smollm-360m").reduced()
    opt = OptConfig(lr=1e-3, warmup_steps=1)
    data = SyntheticLMStream(LMDataConfig(vocab_size=cfg.vocab_size,
                                          seq_len=32, global_batch=8))
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    s1 = init_train_state(cfg, opt, jax.random.key(0))
    s2 = init_train_state(cfg, opt, jax.random.key(0))
    full = make_train_step(cfg, opt, microbatches=1)
    micro = make_train_step(cfg, opt, microbatches=4)
    s1, m1 = jax.jit(full)(s1, batch)
    s2, m2 = jax.jit(micro)(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-3)


def test_loss_decreases_over_short_run():
    from repro.launch import train as train_mod
    losses = train_mod.main(["--arch", "smollm-360m-reduced",
                             "--steps", "30", "--batch", "8", "--seq", "64",
                             "--lr", "1e-2"])
    assert losses[-1] < losses[0] - 0.4, (losses[0], losses[-1])


def test_error_feedback_residual_is_exact():
    grads = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 1, 64),
                              jnp.float32)}
    err = init_error_state(grads)
    deq, new_err = apply_error_feedback(grads, err)
    np.testing.assert_allclose(np.asarray(deq["w"] + new_err["w"]),
                               np.asarray(grads["w"]), atol=1e-6)


def test_compressed_allreduce_single_device_identity():
    from repro.training.grad_compress import make_compressed_allreduce
    mesh = jax.make_mesh((1,), ("data",))
    fn = make_compressed_allreduce(mesh)
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(0, 1, (8, 16)),
                          jnp.float32)}
    out = fn(g)
    # int8 quantization error only (scale = max/127)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=float(jnp.abs(g["w"]).max()) / 100)


def test_straggler_detection():
    t = StepTimer(warmup=3)
    flagged = [t.observe(i, 1.0 + 0.01 * i) for i in range(10)]
    assert not any(flagged)
    assert t.observe(10, 10.0)  # 10x blowup flagged


def test_host_monitor():
    m = HostMonitor()
    for i in range(10):
        m.observe("h0", 1.0)
        m.observe("h1", 1.05)
        m.observe("h2", 2.5)
    assert m.stragglers() == ["h2"]


def test_elastic_plan():
    from repro.training.elastic import plan_remesh
    plan = plan_remesh(device_count=1, model_parallel=1, old_data_parallel=4)
    assert plan.microbatch_scale == 4
    with pytest.raises(ValueError):
        # model axis cannot exceed the surviving device count
        plan_remesh(device_count=1, model_parallel=2, old_data_parallel=4)
