"""The float32 reference follows the program's cycle, at a tiny size."""

import jax
import numpy as np
import pytest

from tiny_cell import CELL, make

from bench import cells, harness
from bench.reference import compare


@pytest.mark.parametrize("config", ["dqn-nature", "rainbow-nature"])
def test_reference_follows_the_program(tmp_path, config):
    from repro.api import ExperimentSpec, build_trainer
    cell = cells.load_cell(CELL, make(tmp_path, config))
    spec = ExperimentSpec.from_dict(cells.spec_dict(cell, 2 ** 31 + 99))
    _, probe = harness.first_cycles(build_trainer(spec))
    ref = harness.reference_runs(cell, [spec.seed], jax.devices()[:1])[0]
    got = compare.readings(probe.replica(0), ref)
    # on the CPU both compute in float32: only the order of sums differs
    assert max(got.values()) < 1e-5, got
    assert probe.replica(0)["loss"] == pytest.approx(ref["loss"], rel=1e-5)


@pytest.mark.parametrize("config", ["dqn-nature", "rainbow-nature"])
def test_seed_argument_init_is_the_trainers_init(tmp_path, config):
    from repro.api import ExperimentSpec, build_trainer
    cell = cells.load_cell(CELL, make(tmp_path, config))
    trainers = [build_trainer(ExperimentSpec.from_dict(
        cells.spec_dict(cell, seed))) for seed in (2 ** 31 + 5, 17)]
    for t in trainers:
        got, want = harness.init_carry(t), t.init_carry()
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # one program for every seed: the seeds are an argument, not constants
    texts = {harness.seed_init(t).lower(np.asarray(t.seeds)).as_text()
             for t in trainers}
    assert len(texts) == 1
