"""The output check's control: the reference computed in bfloat16, put
in the program's place, must fail the committed limits. On the chip
this was read at each cell's own size (PERF.md §2); here it runs at a
tiny size on the CPU."""

import json

import pytest

from tiny_cell import CELL, ROOT, make

from bench import calibrate


@pytest.mark.parametrize("config", ["dqn-nature", "rainbow-nature"])
def test_bfloat16_reference_fails_the_limits(tmp_path, config):
    limits = json.loads((ROOT / "bench" / "limits" / f"{config}.json")
                        .read_text())["limits"]
    bench = make(tmp_path, config)
    (line,) = calibrate.calibrate(CELL, [2 ** 31 + 7], ["reference_bf16"],
                                  root=bench, require_tpu=False)
    got = line["readings"]
    assert any(got[k] > limits[k] for k in limits), (got, limits)
