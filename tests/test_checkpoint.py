"""Checkpoint/restart fault tolerance: atomicity, integrity, resume, reshard."""

import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import checkpoint as ckpt


def _state(seed=0):
    k = jax.random.key(seed)
    return {"w": jax.random.normal(k, (8, 16)),
            "blocks": ({"a": jnp.arange(12.0).reshape(3, 4)},
                       {"a": jnp.ones((3, 4))}),
            "step": jnp.asarray(7, jnp.int32)}


def test_save_restore_roundtrip(tmp_path):
    s = _state()
    ckpt.save(s, tmp_path, step=10)
    r = ckpt.restore(s, tmp_path)
    for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_step_and_retention(tmp_path):
    s = _state()
    for step in (1, 5, 3, 9):
        ckpt.save(s, tmp_path, step=step, keep_last=2)
    assert ckpt.latest_step(tmp_path) == 9
    assert ckpt.all_steps(tmp_path) == [5, 9]


def test_crash_mid_save_is_invisible(tmp_path):
    """A .tmp directory (simulated crash) must never be picked up."""
    s = _state()
    ckpt.save(s, tmp_path, step=4)
    fake = tmp_path / "step_000009.tmp.deadbeef"
    fake.mkdir()
    (fake / "leaf_00000.npy").write_bytes(b"garbage")
    assert ckpt.latest_step(tmp_path) == 4
    ckpt.restore(s, tmp_path)  # restores step 4, not the wreck


def test_integrity_check_detects_corruption(tmp_path):
    s = _state()
    d = ckpt.save(s, tmp_path, step=2)
    leaf = d / "leaf_00000.npy"
    arr = np.load(leaf)
    arr.ravel()[0] += 1.0
    np.save(leaf, arr)
    with pytest.raises(IOError, match="integrity"):
        ckpt.restore(s, tmp_path)


def test_restore_onto_different_sharding(tmp_path):
    """The elastic path: save on one layout, restore onto another mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    s = _state()
    ckpt.save(s, tmp_path, step=1)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = jax.tree.map(lambda l: NamedSharding(mesh, P()), s)
    r = ckpt.restore(s, tmp_path, shardings=sh)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(s["w"]))


def test_async_checkpointer(tmp_path):
    s = _state()
    saver = ckpt.AsyncCheckpointer(tmp_path, keep_last=2)
    for step in (1, 2, 3):
        saver.save(s, step)
    saver.wait()
    assert ckpt.latest_step(tmp_path) == 3
    assert len(ckpt.all_steps(tmp_path)) == 2


def test_train_resume_equivalence(tmp_path):
    """Kill/restart: N steps straight == N/2 steps + restart + N/2 steps."""
    from repro.launch import train as train_mod
    args = ["--arch", "smollm-360m-reduced", "--batch", "4",
            "--seq", "32", "--lr", "1e-3"]
    losses_straight = train_mod.main(args + ["--steps", "6"])
    ck = str(tmp_path / "ck")
    train_mod.main(args + ["--steps", "3", "--ckpt", ck,
                           "--ckpt-every", "100"])
    losses_resumed = train_mod.main(args + ["--steps", "3", "--ckpt", ck,
                                            "--ckpt-every", "100"])
    np.testing.assert_allclose(losses_straight[3:], losses_resumed,
                               rtol=1e-4, atol=1e-5)
