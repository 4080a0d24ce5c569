"""A configuration brings its own reference and FLOP count as files of
its own, found by its name in the checkout the harness is given; every
other configuration takes the shared ``reference/cycle.py`` and
``counts/network.py`` of that checkout."""

import textwrap

import pytest

from tiny_cell import CELL, add_config, make

from bench import cells, counts, harness

# a configuration's own reference, subclassing the checkout's shared one
OWN_REFERENCE = textwrap.dedent('''
    from pathlib import Path

    from bench import cells

    Shared = cells.shared_reference(Path(__file__).resolve().parents[1])


    class Reference(Shared):
    {body}
    ''')

UNCHANGED = "    pass"
# the reference's step made half as long: half the learning rate
HALF_LEARNING_RATE = """\
    def opt_update(self, grads, st, params):
        new, st = super().opt_update(grads, st, params)
        return {k: params[k] + 0.5 * (new[k] - params[k])
                for k in new}, st"""


def _run(bench, cell):
    return harness.run(cell, 2 ** 32 + 11, 0.5, False, root=bench,
                       require_tpu=False)


@pytest.mark.parametrize("body,correct", [(UNCHANGED, True),
                                          (HALF_LEARNING_RATE, False)],
                         ids=["unchanged", "half_learning_rate"])
def test_run_follows_the_configurations_own_reference(tmp_path, body,
                                                      correct):
    bench = make(tmp_path, "dqn-nature")
    cell = add_config(bench, "tiny-own",
                      reference=OWN_REFERENCE.format(body=body))
    assert cells.config_file("reference", "tiny-own", bench) == (
        bench / "reference" / "tiny-own.py")
    res = _run(bench, cell)
    assert res["correct"] is correct, res["checks"]
    if not correct:
        assert res["checks"]["change_gap"]["value"] > (
            res["checks"]["change_gap"]["limit"])


def test_run_takes_the_checkouts_shared_reference(tmp_path):
    bench = make(tmp_path, "dqn-nature")
    (bench / "reference" / "cycle.py").write_text(
        "raise RuntimeError('planted: the checkout\\'s cycle.py ran')\n")
    with pytest.raises(RuntimeError, match="planted"):
        _run(bench, CELL)


FIXED_FLOPS = 1.25e9


@pytest.mark.parametrize("own", [True, False], ids=["own", "shared"])
def test_cycle_mfu_reads_the_configurations_own_count(tmp_path, own):
    bench = make(tmp_path, "rainbow-nature")
    cell = add_config(bench, "tiny-own", count=(
        f"def flops_per_env_step(config, n_actions):\n"
        f"    return {FIXED_FLOPS!r}\n") if own else "")
    cell = cells.load_cell(cell, bench)
    peak = counts.peaks("TPU v5 lite")
    got = cells.load_reader("cycle_mfu", bench)(
        {"cell": cell, "env_steps_per_s": 10_000.0, "chips": 1,
         "peak": peak, "n_actions": 3})
    flops = FIXED_FLOPS if own else cells.load_module(
        bench / "counts" / "network.py").flops_per_env_step(cell.config, 3)
    assert got == 100.0 * flops * 10_000.0 / (1 * peak["bf16_flops_per_s"])
