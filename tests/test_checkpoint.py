"""Checkpoint integrity and recovery: per-array checksums, verify-on-restore,
walk-back past corrupt/mismatched/torn steps, and the async-save crash
window.  Corruption tests carry ``@pytest.mark.faults``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointManager,
    list_steps,
    restore_latest,
    save_checkpoint,
)
from repro.testing import flip_bits, make_torn_tmp, tamper_array, tear_checkpoint


def tree_a(offset=0.0):
    return {"w": jnp.arange(12.0).reshape(3, 4) + offset,
            "b": jnp.ones((4,)) * (1.0 + offset)}


TEMPLATE = {"w": jnp.zeros((3, 4)), "b": jnp.zeros((4,))}


def step_dir(d, step):
    return os.path.join(d, f"step_{step:09d}")


def test_roundtrip_with_checksums(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 100, tree_a())
    import json
    with open(os.path.join(step_dir(d, 100), "manifest.json")) as f:
        manifest = json.load(f)
    assert set(manifest["checksums"]) == {"w", "b"}
    step, restored = restore_latest(d, TEMPLATE)
    assert step == 100
    np.testing.assert_allclose(restored["w"], np.asarray(tree_a()["w"]))
    np.testing.assert_allclose(restored["b"], np.asarray(tree_a()["b"]))


@pytest.mark.faults
def test_checksum_mismatch_walks_back(tmp_path, caplog):
    """Silent data corruption (array changed, archive still readable, manifest
    intact) must be caught by checksum verification and demoted to the
    next-older step."""
    d = str(tmp_path)
    save_checkpoint(d, 100, tree_a())
    save_checkpoint(d, 200, tree_a(1.0))
    tamper_array(step_dir(d, 200))
    with caplog.at_level("WARNING", logger="repro.checkpoint"):
        step, restored = restore_latest(d, TEMPLATE)
    assert step == 100
    np.testing.assert_allclose(restored["w"], np.asarray(tree_a()["w"]))
    assert any("checksum mismatch" in r.message for r in caplog.records)
    # escape hatch: verification off restores the tampered newest step
    step_nv, _ = restore_latest(d, TEMPLATE, verify=False)
    assert step_nv == 200


@pytest.mark.faults
def test_bitflipped_npz_walks_back(tmp_path):
    """Raw bit flips in arrays.npz — whether they break the zip structure or
    the payload, restore must recover the older step, never raise."""
    d = str(tmp_path)
    save_checkpoint(d, 100, tree_a())
    save_checkpoint(d, 200, tree_a(1.0))
    flip_bits(os.path.join(step_dir(d, 200), "arrays.npz"), n_bits=16, seed=3)
    step, restored = restore_latest(d, TEMPLATE)
    assert step == 100
    np.testing.assert_allclose(restored["b"], np.asarray(tree_a()["b"]))


@pytest.mark.faults
def test_template_keyset_mismatch_walks_back(tmp_path, caplog):
    """A structurally incompatible checkpoint (e.g. from an older model
    revision) used to raise ValueError mid-walk; it must log and continue."""
    d = str(tmp_path)
    save_checkpoint(d, 100, tree_a())
    save_checkpoint(d, 200, {"other": jnp.zeros((2,))})
    with caplog.at_level("WARNING", logger="repro.checkpoint"):
        step, restored = restore_latest(d, TEMPLATE)
    assert step == 100
    assert any("mismatch" in r.message for r in caplog.records)


@pytest.mark.faults
def test_torn_manifest_never_listed(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 100, tree_a())
    save_checkpoint(d, 200, tree_a(1.0))
    tear_checkpoint(step_dir(d, 200))
    assert list_steps(d) == [100]
    step, _ = restore_latest(d, TEMPLATE)
    assert step == 100


def test_nothing_valid_returns_template(tmp_path):
    d = str(tmp_path)
    step, restored = restore_latest(d, TEMPLATE)
    assert step is None and restored is TEMPLATE
    save_checkpoint(d, 100, tree_a())
    tear_checkpoint(step_dir(d, 100))
    step, restored = restore_latest(d, TEMPLATE)
    assert step is None and restored is TEMPLATE


def test_pre_checksum_checkpoint_still_restores(tmp_path):
    """Back-compat: a manifest without a ``checksums`` entry (older format)
    restores without verification errors."""
    import json
    d = str(tmp_path)
    save_checkpoint(d, 100, tree_a())
    mpath = os.path.join(step_dir(d, 100), "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["checksums"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    step, _ = restore_latest(d, TEMPLATE)
    assert step == 100


@pytest.mark.faults
def test_async_crash_window_recovery(tmp_path):
    """CheckpointManager async path: a process killed between ``maybe_save``
    and ``wait`` leaves only ``.tmp`` junk.  The next save must prune it,
    and ``restore_latest`` must keep finding the previous valid step both
    before and after that save."""
    d = str(tmp_path)
    mgr = CheckpointManager(d, every=100, keep=3, async_save=True)
    assert mgr.maybe_save(100, tree_a())
    mgr.wait()
    # simulated crash mid-save of step 200: torn .tmp, no committed dir
    make_torn_tmp(d, 200)
    assert any(n.endswith(".tmp") for n in os.listdir(d))
    step, _ = restore_latest(d, TEMPLATE)
    assert step == 100                       # junk never considered
    assert mgr.maybe_save(300, tree_a(2.0))
    mgr.wait()
    assert not any(n.endswith(".tmp") for n in os.listdir(d))
    step, restored = restore_latest(d, TEMPLATE)
    assert step == 300
    np.testing.assert_allclose(restored["w"], np.asarray(tree_a(2.0)["w"]))


def test_manager_off_cycle_step_skips(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every=100, async_save=False)
    assert not mgr.maybe_save(101, tree_a())
    assert list_steps(str(tmp_path)) == []


def test_keep_prunes_oldest(tmp_path):
    d = str(tmp_path)
    for s in (100, 200, 300, 400):
        save_checkpoint(d, s, tree_a(float(s)), keep=2)
    assert list_steps(d) == [300, 400]


@pytest.mark.faults
def test_all_recent_corrupt_walks_to_oldest(tmp_path):
    """Multiple consecutive corrupt steps: the walk continues until a valid
    one is found."""
    d = str(tmp_path)
    save_checkpoint(d, 100, tree_a())
    save_checkpoint(d, 200, tree_a(1.0))
    save_checkpoint(d, 300, tree_a(2.0))
    tamper_array(step_dir(d, 300))
    flip_bits(os.path.join(step_dir(d, 200), "arrays.npz"), n_bits=16, seed=5)
    step, restored = restore_latest(d, TEMPLATE)
    assert step == 100
    np.testing.assert_allclose(restored["w"], np.asarray(tree_a()["w"]))


# ---------------------------------------------------------------------------
# roundtrip, keep-k, torn and corrupt saves on a nested tree
# ---------------------------------------------------------------------------

def _tree():
    return {"a": jnp.arange(6.0).reshape(2, 3),
            "nested": {"b": jnp.ones(4, jnp.int32)},
            "lst": [jnp.zeros(2), jnp.full(3, 7.0)]}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 7, t)
    step, restored = restore_latest(str(tmp_path), jax.tree.map(jnp.zeros_like, t))
    assert step == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_k_and_torn_save(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), s, t, keep=2)
    assert list_steps(str(tmp_path)) == [3, 4]
    # simulate a torn save: .tmp dir + corrupt latest
    os.makedirs(tmp_path / "step_000000009.tmp")
    os.makedirs(tmp_path / "step_000000005")  # no manifest → invalid
    step, _ = restore_latest(str(tmp_path), t)
    assert step == 4  # falls back past the invalid one


def test_checkpoint_corrupt_arrays_fall_back(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    save_checkpoint(str(tmp_path), 2, t, keep=5)
    # corrupt step 2's array file
    with open(tmp_path / "step_000000002" / "arrays.npz", "wb") as f:
        f.write(b"garbage")
    step, restored = restore_latest(str(tmp_path), t)
    assert step == 1


def test_checkpoint_manager_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every=2, keep=2, async_save=True)
    t = _tree()
    assert not mgr.maybe_save(1, t)
    assert mgr.maybe_save(2, t)
    mgr.wait()
    assert list_steps(str(tmp_path)) == [2]
