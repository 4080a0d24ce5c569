"""``r2d2-nature``'s own reference against the program, and the
configuration's constants against the program's.

The reference (``bench/reference/r2d2-nature.py``) runs at a tiny size
beside the program's population trainer, through the harness's own
output check and the cell's limits: 10x10 frames, a one-conv torso, an
LSTM of 16, W=4 streams, sequences of 4 + 8 + 1 steps starting every 4
steps, and a target synced every second cycle."""

import inspect

import pytest

from sequence_faults import half_sequence_batch
from tiny_r2d2 import CELL, CONFIG, make

from bench import cells, harness


@pytest.mark.parametrize("seed", [2 ** 32 + 11, 3_000_000_019])
def test_reference_agrees_with_the_program(tmp_path, seed):
    bench = make(tmp_path)
    res = harness.run(CELL, seed, 0.2, False, root=bench, require_tpu=False)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(cells.load_cell(
        CELL, bench).limits["limits"])


DOUBLE_LEARNING_RATE = '''
from pathlib import Path

from bench import cells

Own = cells.load_module(Path(__file__).resolve().parent / "r2d2-nature.py")


class Reference(Own.Reference):
    def opt_update(self, grads, st, params):
        new, st = super().opt_update(grads, st, params)
        return {k: params[k] + 2.0 * (new[k] - params[k]) for k in new}, st
'''


def test_a_wrong_reference_fails_the_check(tmp_path):
    """The check is not blind to the learner: a reference whose steps
    are twice as long fails one of the cell's limits."""
    bench = make(tmp_path)
    (bench / "reference" / "tiny-r2d2.py").write_text(DOUBLE_LEARNING_RATE)
    res = harness.run(CELL, 2 ** 32 + 11, 0.2, False, root=bench,
                      require_tpu=False)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_a_half_batch_learner_fails_the_check(tmp_path):
    """The fault ``bench/calibrate.py`` cannot plant in a sequence
    replay: a learner that trains on the first half of each draw's
    sequences fails one of the cell's limits."""
    bench = make(tmp_path)
    with half_sequence_batch():
        res = harness.run(CELL, 2 ** 32 + 11, 0.2, False, root=bench,
                          require_tpu=False)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_config_network_and_constants_are_the_programs():
    """The reference reads the network and the constants from the
    configuration file; they must be what the program builds."""
    import jax
    import numpy as np

    from repro.api import ExperimentSpec, build_trainer
    from repro.api.nets import r2d2_config
    from repro.core.concurrent import actor_epsilons
    from repro.models import r2d2
    from repro.optim import adamw

    cell = cells.load_cell("r2d2-nature.w256")
    cfg = cell.config
    spec = ExperimentSpec.from_dict(cells.spec_dict(cell, 1))
    net = r2d2_config(spec)
    want = cfg["network"]
    assert [list(c) for c in net.convs] == want["convs"]
    assert (net.frame_size, net.frame_stack, net.lstm,
            net.dueling_hidden) == (want["frame_size"], want["frame_stack"],
                                    want["lstm"], want["dueling_hidden"])
    k = cfg["program_constants"]
    assert k["adam_b1"] == inspect.signature(adamw).parameters["b1"].default
    assert k["lstm_forget_bias"] == r2d2.FORGET_BIAS
    assert spec.dqn_config().target_cycles == cfg["spec"]["schedule"][
        "target_cycles"]

    Reference = cells.load_reference(CONFIG)
    ref = Reference(cfg, cell.envs)
    shapes = {name: s[0] for name, s in ref.param_shapes().items()}
    program = jax.eval_shape(build_trainer(spec)._c.q_init,
                             jax.random.PRNGKey(0))
    assert shapes == {name: x.shape for name, x in program.items()}
    np.testing.assert_allclose(ref.stream_eps,
                               actor_epsilons(spec.variant, cell.envs),
                               rtol=1e-6)
