"""The half-batch fault planted in the sequence replay.

    python tests/bench/sequence_faults.py --workload r2d2-nature.w256 \
        --seeds 1,2,3 --runs program,reference_bf16,half_batch [--out FILE]

``bench/calibrate.py``'s ``half_batch`` fault wraps the transition
samplers, so it plants nothing in a recurrent cycle.
``half_sequence_batch()`` plants it in the sequence replay instead: each
draw keeps the first half of its sequences, repeated to the minibatch's
size, so the learner trains on half as many sequences. Run as a script,
this is ``bench/calibrate.py`` with its ``half_batch`` run so planted:
one JSON line per (seed, run), every run of a seed against one float32
reference.
"""

import contextlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


@contextlib.contextmanager
def half_sequence_batch():
    """The recurrent cycle's sequence draws keep their first half."""
    from repro.core import concurrent

    saved = concurrent.sequence_sample

    def half(*args, **kwargs):
        batch = saved(*args, **kwargs)
        return {k: _first_half_twice(v) for k, v in batch.items()}

    concurrent.sequence_sample = half
    try:
        yield
    finally:
        concurrent.sequence_sample = saved


def _first_half_twice(x):
    import jax.numpy as jnp
    half = x[:x.shape[0] // 2]
    return jnp.concatenate([half, half])


def main(argv=None) -> int:
    from bench import calibrate
    calibrate.half_batch = half_sequence_batch
    return calibrate.main(argv)


if __name__ == "__main__":
    sys.exit(main())
