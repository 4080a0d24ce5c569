"""A cell of P=4 replicas on four devices: the harness runs the sharded
fleet and checks every replica against its own reference run. Four
virtual CPU devices stand in for the chips, in a child process, since
the device count is fixed when JAX starts."""

import json
import os
import subprocess
import sys

from tiny_cell import ROOT

SCRIPT = r"""
import json, sys
from pathlib import Path
sys.path[:0] = [{src!r}, {root!r}, {tests!r}]
from tiny_cell import make
bench = make(Path({tmp!r}), "dqn-nature")
(bench / "traffic" / "tiny-fleet4.json").write_text(json.dumps(
    {{"name": "tiny-fleet4", "replicas": 4, "envs": 4, "env": "pong"}}))
(bench / "workloads" / "tiny.fleet4.json").write_text(json.dumps(
    {{"name": "tiny.fleet4", "config": "tiny", "traffic": "tiny-fleet4",
      "chips": 4, "why": "fleet harness test"}}))
from bench import harness
res = harness.run("tiny.fleet4", 2 ** 31 + 11, 0.5, False, root=bench,
                  require_tpu=False)
print(json.dumps(res))
"""


def test_fleet_of_four_on_four_devices(tmp_path):
    code = SCRIPT.format(src=str(ROOT / "src"), root=str(ROOT),
                         tests=str(ROOT / "tests" / "bench"),
                         tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert res["attempted"] % 4 == 0
