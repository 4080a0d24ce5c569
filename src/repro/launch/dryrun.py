import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers, SPMD-partitions, and compiles, and extract the
roofline terms from the compiled artifact.

The two lines above MUST run before any other import (jax locks the
device count at first init). 512 host devices back both meshes: the
16x16 single-pod mesh uses the first 256; the 2x16x16 multi-pod mesh
uses all 512.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh single --out results/dryrun.json
Options --fsdp / --no-remat / --variant tag support the §Perf
iterations; results append incrementally (resume-safe).
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ExecConfig, INPUT_SHAPES, TrainConfig
from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (decode_cache_len, decode_is_ring, input_specs,
                                needs_memory)
from repro.launch.steps import (abstract_cache, abstract_train_state,
                                make_prefill_step, make_serve_step,
                                make_train_step)
from repro.sharding.rules import (batch_axes, cache_shardings,
                                  input_shardings, param_shardings)
from repro.roofline.analysis import collective_bytes, model_flops, roofline_terms


def make_mesh(multi_pod: bool):
    if multi_pod:
        return make_production_mesh(multi_pod=True)
    devices = jax.devices()[:256]
    import numpy as np
    return jax.sharding.Mesh(np.array(devices).reshape(16, 16),
                             ("data", "model"))


def lower_one(arch: str, shape_name: str, multi_pod: bool,
              ec: ExecConfig, tc: TrainConfig) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_mesh(multi_pod)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": 512 if multi_pod else 256,
    }

    with jax.set_mesh(mesh):
        pshard = param_shardings(cfg, mesh, ec)
        t0 = time.time()
        if shape.kind == "train":
            step, opt = make_train_step(cfg, ec, tc)
            params, opt_state = abstract_train_state(cfg, ec, tc)
            # opt state mirrors params under m/v; "step" scalar replicated
            oshard = shard_like_params(opt_state, pshard, mesh)
            ishard = input_shardings(cfg, mesh, shape.global_batch,
                                     needs_memory(cfg))
            ishard = {k: v for k, v in ishard.items()
                      if k in input_specs(cfg, ec, shape_name)}
            fn = jax.jit(step, in_shardings=(pshard, oshard, ishard),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params, opt_state, input_specs(cfg, ec, shape_name))
            tokens = shape.global_batch * shape.seq_len
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, ec)
            from repro.models.transformer import abstract_params
            params = abstract_params(cfg, ec)
            specs = input_specs(cfg, ec, shape_name)
            ishard = {k: v for k, v in input_shardings(
                cfg, mesh, shape.global_batch, needs_memory(cfg)).items()
                if k in specs}
            fn = jax.jit(step, in_shardings=(pshard, ishard))
            lowered = fn.lower(params, specs)
            tokens = shape.global_batch * shape.seq_len
        else:  # decode
            ring = decode_is_ring(shape)
            step = make_serve_step(cfg, ec, ring=ring)
            from repro.models.transformer import abstract_params
            params = abstract_params(cfg, ec)
            specs = input_specs(cfg, ec, shape_name)
            cshard = cache_shardings(cfg, mesh, ec, shape.global_batch,
                                     specs["cache"])
            b = batch_axes(mesh, shape.global_batch)
            tshard = NamedSharding(mesh, P(b, None))
            fn = jax.jit(step, in_shardings=(pshard, cshard, tshard),
                         donate_argnums=(1,))
            lowered = fn.lower(params, specs["cache"], specs["tokens"])
            tokens = shape.global_batch
        rec["lower_s"] = round(time.time() - t0, 2)

        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

    # built-in cost_analysis does NOT multiply while-loop bodies by trip
    # count (verified) — use the HLO cost walker; keep builtin for cross-ref
    from repro.roofline.hlo_cost import analyze_text
    hlo = analyze_text(compiled.as_text())
    rec["flops_per_device"] = hlo["flops"]
    rec["bytes_per_device"] = hlo["bytes"]
    ca = compiled.cost_analysis() or {}
    rec["builtin_flops_unrolled_once"] = float(ca.get("flops", 0.0))
    ma = compiled.memory_analysis()
    if ma is not None:
        rec["mem"] = {
            "argument_mb": ma.argument_size_in_bytes / 1e6,
            "output_mb": ma.output_size_in_bytes / 1e6,
            "temp_mb": ma.temp_size_in_bytes / 1e6,
            "alias_mb": ma.alias_size_in_bytes / 1e6,
        }
        rec["hbm_gb_per_device"] = (
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 1e9
    rec["collectives"] = {k: v for k, v in hlo["collectives"].items() if v}
    coll_total = hlo["collective_bytes"]
    rec["collective_bytes_per_device"] = coll_total
    rec.update(roofline_terms(rec["flops_per_device"],
                              rec["bytes_per_device"], coll_total))
    useful, total_p, active_p = model_flops(
        cfg, tokens, "train" if shape.kind == "train" else "infer")
    rec["model_flops_global"] = useful
    rec["params_total"] = total_p
    rec["params_active"] = active_p
    global_flops = rec["flops_per_device"] * rec["n_chips"]
    rec["useful_ratio"] = useful / global_flops if global_flops else 0.0
    return rec


def dqn_variant_spec(variant_name: str, kernel_backend: str,
                     mode: str = "concurrent", env: str = "catch",
                     obs_mode: str = "pixels"):
    """The dryrun-sized ExperimentSpec for one variant preset: the
    ``tiny`` network (or its ``mlp_tiny`` vector-mode analogue) on
    catch, a 32-step cycle — seconds to compile. Shared with tests so
    the dryrun grid and the test harness cannot drift."""
    from repro.api import AlgoSpec, ExperimentSpec, ScheduleSpec
    from repro.configs.dqn_nature import get_variant

    return ExperimentSpec(
        env=env, mode=mode, variant=get_variant(variant_name),
        obs_mode=obs_mode,
        envs=4, frame_size=10,
        net="mlp_tiny" if obs_mode == "vector" else "tiny",
        schedule=ScheduleSpec(cycles=1, cycle_steps=32, prepopulate=64,
                              eval_every=1, eval_episodes=8),
        algo=AlgoSpec(minibatch_size=8, replay_capacity=512,
                      train_period=4, eps_anneal_steps=1000),
        exec=ExecConfig(compute_dtype="float32",
                        kernel_backend=kernel_backend))


def lower_dqn_variant(variant_name: str, kernel_backend: str,
                      env: str = "catch",
                      obs_mode: str = "pixels") -> Dict[str, Any]:
    """Lower + compile one off-policy DQN variant's jitted C-cycle (the
    concurrent super-step, including the PER segment-tree path) and
    extract the same roofline terms as the LLM shapes. Single-device:
    the DQN reproduction targets commodity hosts, not the pod mesh.
    Construction goes through ``repro.api.build_trainer`` — the same
    path as rl_train — so what the dryrun proves compilable is exactly
    what the launcher runs."""
    from repro.api import build_trainer

    trainer = build_trainer(dqn_variant_spec(variant_name, kernel_backend,
                                             env=env, obs_mode=obs_mode))
    carry = trainer.init_carry()

    rec: Dict[str, Any] = {"arch": "dqn", "shape": f"variant_{variant_name}",
                           "mesh": "1x1", "n_chips": 1}
    t0 = time.time()
    lowered = trainer.cycle.lower(carry)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    from repro.roofline.hlo_cost import analyze_text
    hlo = analyze_text(compiled.as_text())
    rec["flops_per_device"] = hlo["flops"]
    rec["bytes_per_device"] = hlo["bytes"]
    rec["collective_bytes_per_device"] = hlo["collective_bytes"]
    rec.update(roofline_terms(hlo["flops"], hlo["bytes"],
                              hlo["collective_bytes"]))
    return rec


def shard_like_params(opt_state, pshard, mesh):
    """Optimizer state trees mirror the param tree under m/v; scalars
    replicated."""
    rep = NamedSharding(mesh, P())

    def walk(node):
        if isinstance(node, dict) and set(node) >= {"m", "v"}:
            return {"m": pshard, "v": pshard,
                    **{k: rep for k in node if k not in ("m", "v")}}
        return jax.tree.map(lambda _: rep, node)

    return walk(opt_state)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--moe-impl", default="scatter",
                    choices=["scatter", "dense", "expert_parallel"])
    ap.add_argument("--kv-seq-shard", action="store_true")
    ap.add_argument("--slstm-unroll", type=int, default=1)
    ap.add_argument("--mlstm-recurrent", action="store_true")
    ap.add_argument("--decode-repeat-kv", action="store_true")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "pallas", "interpret", "ref",
                             "mosaic", "triton"])
    ap.add_argument("--env", default="catch",
                    help="(--arch dqn) env registry name; unknown names "
                         "fail listing the available games")
    ap.add_argument("--obs-mode", default="pixels",
                    choices=["pixels", "vector"],
                    help="(--arch dqn) observation mode for the variant "
                         "grid")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    # --arch dqn: lower every off-policy DQN variant preset instead of
    # the LLM (arch x shape x mesh) grid; --variant narrows to one preset.
    if args.arch == "dqn":
        from repro.configs.dqn_nature import VARIANTS, get_variant
        from repro.envs import make_env
        try:
            make_env(args.env)       # fail fast, listing available games
        except ValueError as e:
            print(f"invalid --env: {e}", flush=True)
            return 2
        if args.variant == "baseline":        # the LLM-path default tag
            names = sorted(VARIANTS)
        else:
            get_variant(args.variant)         # KeyError on typos, not a sweep
            names = [args.variant]
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        # same resume-safe accumulation as the LLM grid: load, replace
        # matching dqn records, append — never clobber other entries
        results = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                results = json.load(f)
        failed = []
        for name in names:
            print(f"=== dqn x {name}", flush=True)
            try:
                rec = lower_dqn_variant(name, args.kernel_backend,
                                        env=args.env,
                                        obs_mode=args.obs_mode)
                rec["variant"] = name
                print(f"    lower {rec['lower_s']}s compile "
                      f"{rec['compile_s']}s | {rec['flops_per_device']:.3e} "
                      f"flop/dev", flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                # keep the record schema loadable by the LLM-grid branch
                # (it keys on arch/shape/mesh when resuming a shared file)
                rec = {"arch": "dqn", "shape": f"variant_{name}",
                       "mesh": "1x1", "variant": name, "error": str(e),
                       "traceback": traceback.format_exc()[-2000:]}
                failed.append(name)
                print(f"    FAILED [variant={name}]: {e}", flush=True)
            results = [r for r in results
                       if not (r.get("arch") == "dqn"
                               and r.get("variant") == name)]
            results.append(rec)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
        print(f"\n{len(names) - len(failed)} OK, {len(failed)} failed"
              + (f" ({', '.join(failed)})" if failed else ""))
        return 1 if failed else 0

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    ec = ExecConfig(remat=not args.no_remat, fsdp=args.fsdp,
                    moe_impl=args.moe_impl, kv_seq_shard=args.kv_seq_shard,
                    slstm_unroll=args.slstm_unroll,
                    mlstm_chunked=not args.mlstm_recurrent,
                    decode_grouped=not args.decode_repeat_kv,
                    kernel_backend=args.kernel_backend)
    tc = TrainConfig(remat=not args.no_remat)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], r.get("variant", "baseline"))
            for r in results if "error" not in r}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                key = (arch, shape, mesh_name, args.variant)
                if key in done and not args.force:
                    print(f"skip {key} (done)")
                    continue
                print(f"=== {arch} x {shape} x {mesh_name} [{args.variant}]",
                      flush=True)
                try:
                    rec = lower_one(arch, shape, mp, ec, tc)
                    rec["variant"] = args.variant
                    print(f"    lower {rec['lower_s']}s compile {rec['compile_s']}s "
                          f"| {rec['flops_per_device']:.3e} flop/dev "
                          f"| coll {rec['collective_bytes_per_device']:.3e} B "
                          f"| dominant {rec['dominant']}", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "variant": args.variant, "error": str(e),
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"    FAILED: {e}", flush=True)
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"],
                               r.get("variant", "baseline")) != key]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    errs = [r for r in results if "error" in r]
    print(f"\n{len(results) - len(errs)} OK, {len(errs)} failed")
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
