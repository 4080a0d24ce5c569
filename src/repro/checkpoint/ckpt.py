"""Sharding-aware .npz checkpointing.

Flattens a pytree to path-keyed arrays; on restore, arrays are placed
back onto the caller's shardings (``jax.device_put`` with the target
NamedSharding tree), so a checkpoint written on one mesh restores onto
another — the standard reshard-on-restore pattern. Steps are kept under
``<dir>/step_<n>.npz``.

Durability contract (checkpoints are production serving artifacts, not
just a resume convenience — see docs/serving.md):

* writes are atomic AND durable: tmp file, ``fsync`` before the rename,
  ``os.replace``, then an fsync of the directory so the rename itself
  survives a power cut;
* a failed write never leaks its tmp file into the checkpoint dir;
* :func:`restore_latest` walks down from the newest step past any
  checkpoint that cannot be restored (truncated/corrupt/partial), so
  one torn file never blocks ``--resume`` or a policy server boot —
  callers get the skipped paths back to warn about.
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import zipfile
import zlib
from typing import Any, List, Optional, Tuple

import jax
import numpy as np

SEP = "/"

# What a truncated/corrupt .npz surfaces as: zipfile errors on a torn
# archive, zlib/value/EOF errors on a torn member, OSError on unreadable
# files, ValueError also covers template mismatches (restore_latest must
# not "fall back" past a legitimate structural error silently — it
# reports every skipped path so callers can tell the two apart).
RESTORE_ERRORS = (OSError, ValueError, EOFError, KeyError,
                  zipfile.BadZipFile, zlib.error)


def _flatten(tree: Any, prefix: str = ""):
    if tree is None:            # an absent part (a pytree with no leaves)
        return
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flatten(tree[k], f"{prefix}{k}{SEP}")
    elif isinstance(tree, (tuple, list)):
        for i, v in enumerate(tree):
            yield from _flatten(v, f"{prefix}__{i}{SEP}")
    else:
        yield prefix.rstrip(SEP), tree


def save_checkpoint(ckpt_dir: str, step: int, tree: Any) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    arrays = {path: np.asarray(leaf) for path, leaf in _flatten(tree)}
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
            # flush to stable storage BEFORE the rename: os.replace is
            # atomic in the namespace but says nothing about the data —
            # without this, a crash can leave a fully-named step_*.npz
            # holding truncated bytes, which latest_step() then selects
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        # never leak the tmp file into the checkpoint dir on a failed
        # write (np.savez raising used to strand it there forever)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _fsync_dir(ckpt_dir)
    return path


def _fsync_dir(path: str) -> None:
    """Make a completed rename durable (best-effort on platforms whose
    directories cannot be opened/fsynced)."""
    try:
        dfd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(dfd)
    except OSError:
        pass
    finally:
        os.close(dfd)


def _unflatten_into(template: Any, arrays, prefix: str = ""):
    if template is None:
        return None
    if isinstance(template, dict):
        return {k: _unflatten_into(template[k], arrays, f"{prefix}{k}{SEP}")
                for k in template}
    if isinstance(template, (tuple, list)):
        vals = [_unflatten_into(v, arrays, f"{prefix}__{i}{SEP}")
                for i, v in enumerate(template)]
        if hasattr(template, "_fields"):
            # NamedTuples (TrainerCarry, SamplerState, ...) take their
            # fields positionally, not as one iterable
            return type(template)(*vals)
        return type(template)(vals)
    return arrays[prefix.rstrip(SEP)]


def restore_checkpoint(ckpt_dir: str, step: int, template: Any,
                       shardings: Optional[Any] = None) -> Any:
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    with np.load(path) as data:
        arrays = {k: data[k] for k in data.files}
    expected = {p for p, _ in _flatten(template)}
    if expected != set(arrays):
        # a structural mismatch would otherwise surface as an opaque
        # KeyError deep inside _unflatten_into; name the paths instead
        # (launchers additionally guard with the stored ExperimentSpec —
        # see repro.api.check_resume_compat — which yields a field-level
        # diff before the restore is even attempted)
        missing = sorted(expected - set(arrays))
        extra = sorted(set(arrays) - expected)
        detail = []
        if missing:
            detail.append(f"missing from checkpoint: {missing[:8]}")
        if extra:
            detail.append(f"not in template: {extra[:8]}")
        raise ValueError(
            f"checkpoint {path} does not match the restore template "
            f"({'; '.join(detail)}) — was it written by a run with a "
            "different spec?")
    tree = _unflatten_into(template, arrays)
    if shardings is not None:
        tree = jax.tree.map(lambda a, s: jax.device_put(a, s), tree, shardings)
    return tree


def list_steps(ckpt_dir: str) -> List[int]:
    """All checkpointed step numbers in ``ckpt_dir``, ascending."""
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(int(m.group(1)) for f in os.listdir(ckpt_dir)
                  if (m := re.match(r"step_(\d+)\.npz$", f)))


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = list_steps(ckpt_dir)
    return steps[-1] if steps else None


def prune_steps(ckpt_dir: str, keep_last: int = 1) -> List[str]:
    """Delete all but the newest ``keep_last`` checkpoints and return
    the removed paths. Sweep fleets checkpoint every few cycles and a
    large grid would otherwise accumulate every intermediate step on
    disk; once a fleet completes, only the newest checkpoint(s) matter
    for resume. Never removes the newest file, so a concurrent
    ``restore_latest`` always has its first candidate intact."""
    if keep_last < 1:
        raise ValueError(f"keep_last must be >= 1, got {keep_last}")
    removed: List[str] = []
    for step in list_steps(ckpt_dir)[:-keep_last]:
        path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
        try:
            os.unlink(path)
        except OSError:
            continue
        removed.append(path)
    return removed


def trim_metrics_jsonl(path: str, start_cycle: int) -> None:
    """Drop metrics rows with cycle > start_cycle (plus any torn
    trailing line an interrupted run left) so a resumed loop never
    produces two rows per (cycle, replica). The trimmed copy is written
    to a tmp file in the same directory, fsynced and renamed over the
    original — an interrupt mid-trim leaves the full history intact.
    Shared by ``rl_train --resume`` and the sweep runner's per-run
    metrics files."""
    kept = []
    with open(path) as f:
        for ln in f:
            try:
                row = json.loads(ln)
            except ValueError:
                continue
            if row.get("cycle", 0) <= start_cycle:
                kept.append(ln)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".metrics-", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.writelines(kept)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def restore_latest(ckpt_dir: str, template: Any,
                   shardings: Optional[Any] = None
                   ) -> Tuple[Optional[int], Any, List[str]]:
    """Restore the newest *restorable* checkpoint.

    Walks down from the latest step; a checkpoint that fails to restore
    (torn write from a crash, truncated copy, structural mismatch) is
    skipped and the walk continues to the previous step. Returns
    ``(step, tree, skipped)`` where ``skipped`` lists
    ``"<path>: <error>"`` for every file passed over — callers MUST
    surface these (a skipped checkpoint means lost progress and, for a
    template mismatch, possibly the wrong spec). ``(None, None,
    skipped)`` when nothing restores."""
    skipped: List[str] = []
    for step in reversed(list_steps(ckpt_dir)):
        path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
        try:
            return step, restore_checkpoint(ckpt_dir, step, template,
                                            shardings), skipped
        except RESTORE_ERRORS as e:
            skipped.append(f"{path}: {type(e).__name__}: {e}")
    return None, None, skipped
