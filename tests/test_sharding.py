"""Sharding-rule unit tests: divisibility fallbacks, batch axes, and the
multi-device dry-run machinery via a subprocess (so the 512-device flag
never leaks into this process)."""

import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config
from repro.config import ExecConfig

EC = ExecConfig()


class FakeMesh:
    """Just enough Mesh interface for rules (shape/axis_names)."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


def rules_for(arch, **axes):
    from repro.sharding.rules import logical_rules
    return logical_rules(get_config(arch), FakeMesh(**axes), EC)


def test_granite20b_mqa_kv_replicated():
    r = rules_for("granite-20b", data=16, model=16)
    assert r["kv_flat"] is None          # 1 KV head can't shard 16 ways
    assert r["heads_flat"] == "model"    # 48 q heads can
    assert r["mlp"] == "model"


def test_starcoder_heads_not_divisible():
    r = rules_for("starcoder2-3b", data=16, model=16)
    assert r["heads_flat"] is None       # 24 % 16 != 0 -> replicated
    assert r["vocab"] == "model"


def test_qwen_moe_experts_fallback_to_expert_mlp():
    r = rules_for("qwen2-moe-a2.7b", data=16, model=16)
    assert r["experts"] is None          # 60 % 16 != 0
    assert r["expert_mlp"] == "model"    # 1408 % 16 == 0


def test_granite_moe_expert_parallel():
    r = rules_for("granite-moe-1b-a400m", data=16, model=16)
    assert r["experts"] == "model"       # 32 % 16 == 0


def test_zamba_ssm_sharding():
    r = rules_for("zamba2-2.7b", data=16, model=16)
    assert r["ssm_inner"] == "model"     # 5120 % 16 == 0
    assert r["ssm_heads"] == "model"     # 80 % 16 == 0


def test_batch_axes_prefix():
    from repro.sharding.rules import batch_axes
    mesh = FakeMesh(pod=2, data=16, model=16)
    assert batch_axes(mesh, 256) == ("pod", "data")
    assert batch_axes(mesh, 16) == ("pod",)   # 16 % 32 != 0 but 16 % 2 == 0
    assert batch_axes(mesh, 1) is None
    single = FakeMesh(data=16, model=16)
    assert batch_axes(single, 128) == ("data",)


@pytest.mark.slow
def test_dryrun_reduced_multidevice_subprocess(tmp_path):
    """End-to-end dry-run machinery on an 8-device host mesh with a
    reduced arch: lower + compile + roofline extraction must succeed and
    produce collectives."""
    out = tmp_path / "prog.py"
    out.write_text(
        """
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import json
import jax, jax.numpy as jnp
from repro.config import TrainConfig
from repro.configs import reduced_config
from repro.config import ExecConfig
from repro.models import transformer as T
from repro.launch.steps import make_train_step, abstract_train_state
from repro.sharding.rules import param_shardings, input_shardings
from repro.launch.dryrun import shard_like_params
from repro.roofline.hlo_cost import analyze_text

if jax.device_count() < 8:
    print("SKIP: only", jax.device_count(), "devices visible")
    raise SystemExit(0)

cfg = reduced_config("granite-3-8b")
ec = ExecConfig(remat=True)
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    step, opt = make_train_step(cfg, ec, TrainConfig())
    params, opt_state = abstract_train_state(cfg, ec, TrainConfig())
    pshard = param_shardings(cfg, mesh, ec)
    oshard = shard_like_params(opt_state, pshard, mesh)
    ishard = input_shardings(cfg, mesh, 4, False)
    specs = {
        "tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
        "labels": jax.ShapeDtypeStruct((4, 64), jnp.int32),
        "mask": jax.ShapeDtypeStruct((4, 64), jnp.float32),
    }
    fn = jax.jit(step, in_shardings=(pshard, oshard, ishard))
    compiled = fn.lower(params, opt_state, specs).compile()
    a = analyze_text(compiled.as_text())
    print(json.dumps({"flops": a["flops"], "coll": a["collective_bytes"]}))
""")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    env = dict(os.environ, PYTHONPATH="src", XLA_FLAGS=flags)
    res = subprocess.run([sys.executable, str(out)], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    if "SKIP" in res.stdout:
        pytest.skip(res.stdout.strip())
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["coll"] > 0               # model-parallel matmuls all-reduce
