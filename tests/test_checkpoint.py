"""Checkpointing: roundtrip, atomicity, GC, async, elastic restore,
and verified restore (per-shard sha256, corrupt-step fallback)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import (CheckpointCorruptionError,
                                      CheckpointManager)


def _tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "nested": {"b": jnp.ones((2, 2), jnp.int32)}}


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    t = _tree()
    mgr.save(5, t)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), t)
    r = mgr.restore(like)
    np.testing.assert_array_equal(np.asarray(r["a"]), np.asarray(t["a"]))
    np.testing.assert_array_equal(np.asarray(r["nested"]["b"]),
                                  np.asarray(t["nested"]["b"]))
    assert mgr.latest_step() == 5


def test_keep_last_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree())
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path))
    assert steps == [3, 4]


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_async(9, _tree())
    mgr.wait()
    assert mgr.latest_step() == 9


def test_restore_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree())
    bad = {"a": jnp.zeros((5, 5)), "nested": {"b": jnp.zeros((2, 2),
                                                             jnp.int32)}}
    with pytest.raises(ValueError):
        mgr.restore(bad)


# -- verified restore -------------------------------------------------------

def _tree_v(v: float):
    return {"a": jnp.full((3, 4), v, jnp.float32),
            "nested": {"b": jnp.ones((2, 2), jnp.int32)}}


def _like():
    return jax.tree.map(lambda x: jnp.zeros_like(x), _tree_v(0))


def _shard_path(tmp_path, step):
    return os.path.join(str(tmp_path), f"step_{step:010d}",
                        "host_00000.npz")


def _manifest_path(tmp_path, step):
    return os.path.join(str(tmp_path), f"step_{step:010d}",
                        "MANIFEST.json")


def _truncate(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)


@pytest.mark.parametrize("corrupt", ["truncate", "manifest", "checksum"])
def test_corrupt_newest_falls_back_to_previous_step(tmp_path, corrupt):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree_v(1.0))
    mgr.save(2, _tree_v(2.0))
    if corrupt == "truncate":
        _truncate(_shard_path(tmp_path, 2))
    elif corrupt == "manifest":
        with open(_manifest_path(tmp_path, 2), "w") as f:
            f.write("{ this is not json")
    else:  # valid archive, wrong bytes -> checksum mismatch
        np.savez(_shard_path(tmp_path, 2),
                 **{k: np.asarray(v) + 7 for k, v in
                    {"a": _tree_v(2.0)["a"],
                     "nested/b": _tree_v(2.0)["nested"]["b"]}.items()})
    assert not mgr.verify_step(2)
    assert mgr.verify_step(1)
    assert mgr.latest_verifiable_step() == 1
    r = mgr.restore(_like())  # step=None: silent fallback
    np.testing.assert_array_equal(np.asarray(r["a"]),
                                  np.full((3, 4), 1.0, np.float32))
    from repro.obs import get_metrics
    snap = get_metrics().snapshot()
    assert snap["checkpoint.fallback_total"]["value"] == 1
    assert snap["checkpoint.corrupt_total"]["value"] >= 1


def test_explicit_corrupt_step_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree_v(1.0))
    _truncate(_shard_path(tmp_path, 1))
    with pytest.raises(CheckpointCorruptionError):
        mgr.restore(_like(), step=1)


def test_no_verifiable_step_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree_v(1.0))
    _truncate(_shard_path(tmp_path, 1))
    with pytest.raises(CheckpointCorruptionError):
        mgr.restore(_like())


def test_legacy_manifest_without_checksums(tmp_path):
    """Pre-verification checkpoints (no ``checksums`` map) still restore;
    a truncated legacy shard still fails the load-check."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree_v(3.0))
    mpath = _manifest_path(tmp_path, 1)
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["checksums"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    assert mgr.verify_step(1)
    r = mgr.restore(_like())
    np.testing.assert_array_equal(np.asarray(r["a"]),
                                  np.full((3, 4), 3.0, np.float32))
    _truncate(_shard_path(tmp_path, 1))
    assert not mgr.verify_step(1)


def test_gc_keeps_last_known_good(tmp_path):
    """GC never deletes the step the last restore fell back to, even when
    ``keep_last`` would otherwise drop it."""
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    for s in (1, 2, 3):
        mgr.save(s, _tree_v(float(s)))
    _truncate(_shard_path(tmp_path, 3))
    r = mgr.restore(_like())  # falls back to step 2 -> last-known-good
    np.testing.assert_array_equal(np.asarray(r["a"]),
                                  np.full((3, 4), 2.0, np.float32))
    mgr.keep_last = 1
    mgr._gc()
    remaining = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path))
    assert 2 in remaining      # pinned last-known-good survives
    assert 1 not in remaining  # ordinary old step collected


ELASTIC = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager

path, phase = sys.argv[1], sys.argv[2]
mgr = CheckpointManager(path)
t = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
if phase == "save":
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((len(jax.devices()),), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    t = {"w": jax.device_put(t["w"], sh)}
    mgr.save(1, t)
    print("SAVED", len(jax.devices()))
else:
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((len(jax.devices()),), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    like = {"w": jnp.zeros((8, 8), jnp.float32)}
    r = mgr.restore(like, shardings=sh)
    assert np.array_equal(np.asarray(r["w"]),
                          np.arange(64, dtype=np.float32).reshape(8, 8))
    print("RESTORED", len(jax.devices()))
"""


def test_elastic_restore_across_device_counts(tmp_path):
    """Save on 8 'hosts', restore on 4 and on 2 — elastic re-shard."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")

    def run(ndev, phase):
        out = subprocess.run(
            [sys.executable, "-c", ELASTIC % ndev, str(tmp_path), phase],
            capture_output=True, text=True, env=env, timeout=300)
        assert out.returncode == 0, out.stdout + out.stderr
        return out.stdout

    assert "SAVED 8" in run(8, "save")
    assert "RESTORED 4" in run(4, "restore")
    assert "RESTORED 2" in run(2, "restore")
