import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (latest_step, list_steps, restore_checkpoint,
                              restore_latest, save_checkpoint)
from repro.core.concurrent import TrainerCarry
from repro.core.synchronized import SamplerState


def test_roundtrip(tmp_path):
    tree = {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                       "stats": (jnp.ones((2,)), jnp.zeros((), jnp.int32))},
            "step": jnp.int32(7)}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 7, tree)
    save_checkpoint(d, 12, tree)
    assert latest_step(d) == 12
    got = restore_checkpoint(d, 7, tree)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_namedtuple_carry_roundtrip(tmp_path):
    """The PR-4 bugfix: NamedTuple nodes (TrainerCarry, SamplerState)
    must restore by splatting fields — ``type(template)(vals)`` raised
    for every NamedTuple, so no training carry could ever resume."""
    sampler = SamplerState(
        env_states={"ball": jnp.arange(4, dtype=jnp.int32)},
        stack=jnp.ones((4, 10, 10, 2), jnp.uint8),
        key=jax.random.PRNGKey(7))
    carry = TrainerCarry(
        params={"w": jnp.arange(6.0).reshape(2, 3)},
        opt_state={"m": jnp.zeros((2, 3)), "step": jnp.int32(5)},
        replay={"obs": jnp.zeros((8, 10, 10, 2), jnp.uint8),
                "cursor": jnp.int32(3)},
        sampler=sampler, step=jnp.int32(64), seed=jnp.int32(2))
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 64, carry)
    got = restore_checkpoint(d, 64, carry)
    assert isinstance(got, TrainerCarry)
    assert isinstance(got.sampler, SamplerState)
    la, lb = (jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(carry))
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_template_mismatch_names_paths(tmp_path):
    """A checkpoint written under one carry structure restored against
    another must fail by NAMING the mismatched paths — pre-PR-5 this
    surfaced as an opaque KeyError inside the unflatten walk."""
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, {"params": {"w": jnp.ones((2,))}})
    template = {"params": {"w": jnp.ones((2,)),
                           "w_sigma": jnp.ones((2,))}}  # e.g. noisy head
    with pytest.raises(ValueError) as ei:
        restore_checkpoint(d, 1, template)
    assert "params/w_sigma" in str(ei.value)
    assert "different spec" in str(ei.value)


def test_resume_spec_compat_guard(tmp_path):
    """--resume with a spec that mismatches the stored one fails with a
    field-level diff (repro.api guard), and legitimate run extensions
    (more cycles, different eval cadence, moved output paths) stay
    compatible."""
    from repro.api import (ExperimentSpec, SpecCompatError,
                           check_resume_compat, load_run_spec,
                           save_run_spec, spec_compat_diff)

    import repro.api as api

    d = str(tmp_path / "run")
    # eps_anneal_steps pinned: a run must pin its anneal horizon to be
    # extendable (the derived 0-sentinel depends on cycles — see below)
    spec = ExperimentSpec.from_preset(
        "rainbow", seeds=2,
        algo=api.AlgoSpec(eps_anneal_steps=7680))
    save_run_spec(d, spec)
    stored = load_run_spec(d)
    assert stored == spec

    # run extensions and output relocations are NOT incompatibilities
    extended = dataclasses.replace(
        spec,
        schedule=dataclasses.replace(spec.schedule, cycles=999,
                                     eval_every=5),
        checkpoint=dataclasses.replace(spec.checkpoint, dir="elsewhere"))
    assert spec_compat_diff(stored, extended) == []
    check_resume_compat(stored, extended)   # no raise

    # ... but when the anneal horizon is DERIVED (eps_anneal_steps=0),
    # extending cycles silently changes the ε schedule, so the guard
    # materializes the derived value and flags it
    derived = dataclasses.replace(spec, algo=api.AlgoSpec())
    derived_ext = dataclasses.replace(
        derived,
        schedule=dataclasses.replace(derived.schedule, cycles=999))
    diff = spec_compat_diff(derived, derived_ext)
    assert len(diff) == 1 and diff[0].startswith("algo.eps_anneal_steps")

    # structural changes fail with the differing fields named
    changed = dataclasses.replace(
        spec, frame_size=84,
        variant=dataclasses.replace(spec.variant, num_atoms=21))
    with pytest.raises(SpecCompatError) as ei:
        check_resume_compat(stored, changed)
    msg = str(ei.value)
    assert "frame_size: checkpoint=10, requested=84" in msg
    assert "variant.num_atoms: checkpoint=51, requested=21" in msg

    # a compatible re-save leaves the stored file untouched
    save_run_spec(d, extended)
    assert load_run_spec(d) == spec

    # an incompatible spec may replace the stored one ONLY while no
    # checkpoints sit beside it — otherwise a later --resume would
    # restore the old run's carry under the new run's description
    save_run_spec(d, changed)                  # no checkpoints yet: ok
    save_run_spec(d, spec)                     # restore original
    save_checkpoint(d, 20, {"w": jnp.ones((2,))})
    with pytest.raises(SpecCompatError, match="fresh directory"):
        save_run_spec(d, changed)
    assert load_run_spec(d) == spec            # stored spec untouched


def test_restore_latest_walks_past_torn_checkpoint(tmp_path):
    """A checkpoint truncated mid-write (torn) must not block resume:
    restore_latest falls back to the newest step that still restores and
    NAMES the file it skipped."""
    tree = {"w": jnp.arange(4.0)}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, tree)
    save_checkpoint(d, 2, tree)
    p2 = os.path.join(d, "step_00000002.npz")
    with open(p2, "rb") as f:
        head = f.read(57)
    with open(p2, "wb") as f:
        f.write(head)                              # torn: crash mid-write
    assert latest_step(d) == 2
    assert list_steps(d) == [1, 2]
    step, got, skipped = restore_latest(d, tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(got["w"]), np.arange(4.0))
    assert len(skipped) == 1 and "step_00000002.npz" in skipped[0]


def test_restore_latest_nothing_restorable(tmp_path):
    d = str(tmp_path / "ckpt")
    os.makedirs(d)
    for name in ("step_00000001.npz", "step_00000002.npz"):
        with open(os.path.join(d, name), "wb") as f:
            f.write(b"PK\x03\x04 not actually a zip")
    step, got, skipped = restore_latest(d, {"w": jnp.ones((2,))})
    assert step is None and got is None
    assert len(skipped) == 2
    # empty / missing dirs are "fresh run", not errors
    assert restore_latest(str(tmp_path / "nope"), {}) == (None, None, [])


def test_save_failure_leaves_no_debris(tmp_path, monkeypatch):
    """An interrupted save must leave neither a half-written step file
    nor a stray mkstemp tmp behind — the pre-fix bug left the tmp file
    and, worse, a rename of an unsynced file could tear the step."""
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, {"w": jnp.ones((2,))})

    def boom(*a, **kw):
        raise OSError("disk full")

    monkeypatch.setattr(np, "savez", boom)
    with pytest.raises(OSError, match="disk full"):
        save_checkpoint(d, 2, {"w": jnp.ones((2,))})
    assert sorted(os.listdir(d)) == ["step_00000001.npz"]
    assert list_steps(d) == [1]


def test_metrics_trim_is_atomic(tmp_path, monkeypatch):
    """Resume-time JSONL trimming rewrites via tmp+rename: rows past the
    resume cycle (and torn trailing lines) are dropped, and a crash
    mid-trim leaves the ORIGINAL history intact — the pre-fix
    truncating open(..., "w") lost the whole file. (Moved from rl_train
    into repro.checkpoint so the sweep runner shares it.)"""
    from repro.checkpoint import trim_metrics_jsonl

    path = str(tmp_path / "metrics.jsonl")
    rows = [json.dumps({"cycle": c, "loss": 0.1 * c}) + "\n"
            for c in range(1, 6)]
    with open(path, "w") as f:
        f.writelines(rows)
        f.write('{"cycle": 6, "loss"')              # torn trailing line
    trim_metrics_jsonl(path, 3)
    with open(path) as f:
        kept = [json.loads(ln) for ln in f]
    assert [r["cycle"] for r in kept] == [1, 2, 3]

    original = open(path).read()

    def boom(*a, **kw):
        raise OSError("crash mid-trim")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError, match="crash mid-trim"):
        trim_metrics_jsonl(path, 1)
    assert open(path).read() == original            # history survives
    assert os.listdir(tmp_path) == ["metrics.jsonl"]  # no tmp debris


def test_prune_steps_keeps_newest(tmp_path):
    """Fleet-dir housekeeping: prune removes all but the newest
    ``keep_last`` checkpoints, returns the removed paths, never touches
    the newest file, and is a no-op on dirs at/below the floor."""
    from repro.checkpoint import prune_steps

    d = str(tmp_path / "ckpt")
    for step in (1, 2, 5, 9):
        save_checkpoint(d, step, {"w": jnp.full((2,), float(step))})
    removed = prune_steps(d, keep_last=2)
    assert [os.path.basename(p) for p in removed] == [
        "step_00000001.npz", "step_00000002.npz"]
    assert list_steps(d) == [5, 9]
    got = restore_checkpoint(d, 9, {"w": jnp.zeros((2,))})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full((2,), 9.0))
    assert prune_steps(d, keep_last=2) == []       # idempotent at the floor
    assert prune_steps(str(tmp_path / "missing")) == []
    with pytest.raises(ValueError, match="keep_last"):
        prune_steps(d, keep_last=0)


def test_restore_onto_shardings(tmp_path):
    tree = {"w": jnp.arange(8.0)}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, tree)
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"w": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data"))}
    got = restore_checkpoint(d, 1, tree, shardings=sh)
    assert got["w"].sharding == sh["w"]
