"""Checkpoint manager: atomicity, gc, async, elastic restore."""
import json
import shutil
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager


def tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32), "d": jnp.zeros(())}}


def test_save_restore_roundtrip(tmp_path):
    cm = CheckpointManager(tmp_path)
    t = tree()
    cm.save(3, t, meta={"loss": 1.5})
    assert cm.all_steps() == [3]
    like = jax.tree.map(jnp.zeros_like, t)
    r = cm.restore(3, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert cm.manifest(3)["meta"]["loss"] == 1.5


def test_incomplete_checkpoint_ignored(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(1, tree())
    # simulate a preempted save: directory without manifest
    bad = tmp_path / "step_00000002"
    bad.mkdir()
    (bad / "arrays.npz").write_bytes(b"corrupt")
    assert cm.latest_step() == 1


def test_gc_keeps_latest(tmp_path):
    cm = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        cm.save(s, tree())
    assert cm.all_steps() == [3, 4]


def test_async_save(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save_async(7, tree())
    cm.wait()
    assert cm.latest_step() == 7


def test_elastic_restore_with_sharding(tmp_path):
    """Restore onto explicit shardings of the current (1-device) mesh —
    the path a different-size mesh uses after preemption/rescale."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import auto_mesh

    cm = CheckpointManager(tmp_path)
    t = tree()
    cm.save(5, t)
    mesh = auto_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    r = cm.restore(5, t, shardings=sh)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(x.sharding == NamedSharding(mesh, P())
               for x in jax.tree.leaves(r))


def test_train_resume_cli(tmp_path):
    """The train driver resumes exactly where it stopped."""
    from repro.launch.train import main

    args = ["--arch", "brecq_lm_100m", "--reduced", "--steps", "6",
            "--batch", "4", "--seq", "32", "--ckpt-dir", str(tmp_path),
            "--ckpt-every", "3", "--log-every", "100"]
    main(args)
    cm = CheckpointManager(tmp_path)
    assert cm.latest_step() == 6
    # extend the run: resumes from 6, trains to 8
    args8 = [a if a != "6" else "8" for a in args]
    main(args8)
    assert CheckpointManager(tmp_path).latest_step() == 8
    # resume at completion: start_step == steps, the loop never runs —
    # must exit cleanly (regression: NameError on the final save)
    main(args8)
    assert CheckpointManager(tmp_path).latest_step() == 8
