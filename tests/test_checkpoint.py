"""Checkpointer: atomicity, async, corruption tolerance, restore."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, list_checkpoints, restore, save
from repro.launch.mesh import make_mesh


def _state(key, scale=1.0):
    ks = jax.random.split(key, 2)
    return {
        "params": {"w": scale * jax.random.normal(ks[0], (8, 4)),
                   "b": jnp.zeros((4,))},
        "opt": {"step": jnp.int32(3),
                "delta": {"w": scale * jax.random.normal(ks[1], (8, 4)),
                          "b": jnp.zeros((4,))}},
    }


def test_save_restore_roundtrip(tmp_path, key):
    state = _state(key)
    save(str(tmp_path), 7, state, metadata={"arch": "x"})
    got, manifest = restore(str(tmp_path), target=jax.tree.map(
        lambda x: jnp.zeros_like(x), state))
    assert manifest["step"] == 7 and manifest["metadata"]["arch"] == "x"
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_corrupt_checkpoint_skipped(tmp_path, key):
    state = _state(key)
    save(str(tmp_path), 1, state)
    save(str(tmp_path), 2, state)
    # corrupt the newest manifest (simulates crash mid-save)
    with open(tmp_path / "step_0000000002" / "manifest.json", "w") as f:
        f.write("{truncated")
    assert list_checkpoints(str(tmp_path)) == [1]
    got, manifest = restore(str(tmp_path), target=state)
    assert manifest["step"] == 1


def test_restore_shape_mismatch_raises(tmp_path, key):
    save(str(tmp_path), 1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError, match="shape mismatch"):
        restore(str(tmp_path), target={"w": jnp.zeros((5,))})


def test_async_checkpointer_gc_and_wait(tmp_path, key):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    state = _state(key)
    for step in (10, 20, 30):
        ck.save(step, state)
    ck.wait()
    assert list_checkpoints(str(tmp_path)) == [20, 30]


def test_restore_strict_shardings_tree(tmp_path, key):
    """Regression: a shardings tree with fewer leaves than the target
    used to be zip-truncated, silently device_putting the tail of the
    state unsharded. It must error instead."""
    import pytest
    from jax.sharding import NamedSharding, PartitionSpec as P
    state = {"a": jnp.zeros((3,)), "b": jnp.zeros((3,))}
    save(str(tmp_path), 1, state)
    mesh = make_mesh((1,), ("data",))
    short = {"a": NamedSharding(mesh, P())}  # missing "b"
    with pytest.raises(ValueError, match="shardings tree"):
        restore(str(tmp_path), target=state, shardings=short)
    # congruent shardings still restore fine
    full = {"a": NamedSharding(mesh, P()), "b": NamedSharding(mesh, P())}
    got, _ = restore(str(tmp_path), target=state, shardings=full)
    assert jax.tree.leaves(got)[0].sharding == full["a"]


def test_save_best_single_retained(tmp_path, key):
    from repro.checkpoint import restore_best, save_best
    state = _state(key)
    save_best(str(tmp_path), 5, state, metadata={"top1": 0.4})
    save_best(str(tmp_path), 9, _state(key, scale=2.0),
              metadata={"top1": 0.7})
    got, manifest = restore_best(str(tmp_path), target=state)
    assert manifest["step"] == 9
    assert manifest["metadata"]["top1"] == 0.7
    assert list_checkpoints(str(tmp_path / "best")) == [9]
    # best lives outside the rotating window: untouched by main-dir GC
    ck = AsyncCheckpointer(str(tmp_path), keep=1)
    for step in (10, 20):
        ck.save(step, state)
    ck.wait()
    assert list_checkpoints(str(tmp_path)) == [20]
    assert list_checkpoints(str(tmp_path / "best")) == [9]


def test_async_snapshot_isolated_from_donation(tmp_path, key):
    """The snapshot must capture values at call time even if the caller
    mutates/replaces buffers right after (donation semantics)."""
    ck = AsyncCheckpointer(str(tmp_path), keep=1)
    state = {"w": jnp.ones((4,))}
    ck.save(1, state)
    state = {"w": jnp.zeros((4,))}  # overwritten immediately
    ck.wait()
    got, _ = restore(str(tmp_path), target=state)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.ones(4))
