"""One run of one benchmark cell: set-up, the measured window, the
output check and the result line. ``bench/run.py`` is its command line.

The entry the window drives is ``repro.api.build_trainer(spec).cycle``,
the population trainer's compiled C-cycle, called in a loop with the
cycle's metrics fetched to the host after each call, as the training
launcher does. Set-up builds the trainer, makes the carry (replay,
sampler and the prepopulate scan) and drives the cycle through its
first three calls, which the output check compares with the reference.
The window then runs whole cycles until the first cycle boundary after
``seconds``.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from bench import cells
from bench.reference import compare

CHECK_CYCLES = 3          # cycles the reference follows
# cycles in a --trace 1 run's profile: the TPU profiler keeps its events
# in HBM, and a cycle at R=75,000 leaves too little free for two
TRACE_CYCLES = 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Backend-compile seconds and persistent-cache loads reported by
    ``jax.monitoring`` since this counter was made."""

    def __init__(self):
        import jax
        self.backend_compile_s = 0.0
        self.cache_retrieval_s = 0.0
        self.compiles = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.backend_compile_s += secs
                self.compiles += 1
            elif name == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.cache_retrieval_s += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> Dict[str, float]:
        return {"backend_compile_s": self.backend_compile_s,
                "cache_retrieval_s": self.cache_retrieval_s,
                "compiles": self.compiles}


def device_check(chips: int, require_tpu: bool = True):
    """The devices the cell runs on. Exits non-zero, before anything is
    printed on standard output, unless JAX's first device is a TPU and
    it sees at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX's first device is "
                         f"{d.platform!r}, {d.device_kind!r}); the benchmark "
                         "measures only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def _host(tree) -> Dict[str, Any]:
    import jax
    import numpy as np
    return {k: np.asarray(v) for k, v in jax.device_get(tree).items()}


def _moment(opt_state) -> Dict[str, Any]:
    """The optimizer's first-moment state: RMSProp's ``g``, Adam's ``m``."""
    return opt_state["g"] if "g" in opt_state else opt_state["m"]


def _replica(tree: Dict[str, Any], r: int) -> Dict[str, Any]:
    return {k: v[r] for k, v in tree.items()}


@dataclasses.dataclass
class Probe:
    """What the output check keeps of the program's first cycles."""
    loss: List[Any] = dataclasses.field(default_factory=list)
    moment: Optional[Dict[str, Any]] = None
    params0: Optional[Dict[str, Any]] = None
    params3: Optional[Dict[str, Any]] = None

    def replica(self, r: int) -> Dict[str, Any]:
        import numpy as np
        return {"loss": np.asarray([l[r] for l in self.loss]),
                "moment": _replica(self.moment, r),
                "params0": _replica(self.params0, r),
                "params3": _replica(self.params3, r)}


def seed_init(trainer):
    """The trainer's init as one jitted function of its replica seeds,
    or None for a trainer without the parts it is made from.

    ``PopulationTrainer.init_carry`` closes over the seeds as constants,
    so every new seed compiles the init program again (about 22 s on a
    v5e) and set-up would depend on which seeds ran before. This is the
    same init, the program's own replica init vmapped over the seeds,
    with the seeds passed as an argument: one compiled program serves
    every seed."""
    import jax
    from repro.core.population import make_replica_init, population_init
    c = getattr(trainer, "_c", None)
    if c is None or not hasattr(trainer, "_carry_sharding"):
        return None
    init_one = make_replica_init(c.env, c.q_init, c.qf, c.opt, c.dcfg, c.obs)
    return jax.jit(lambda seeds: population_init(init_one, seeds),
                   out_shardings=trainer._carry_sharding)


def init_carry(trainer):
    """The trainer's first carry, made from its replica seeds."""
    import numpy as np
    init = seed_init(trainer)
    if init is None:
        return trainer.init_carry()
    return init(np.asarray(trainer.seeds))


def first_cycles(trainer):
    """Make the carry and drive the trainer's cycle through its first
    ``CHECK_CYCLES`` calls; returns the carry and what the output check
    keeps of them."""
    import jax
    import numpy as np
    carry = init_carry(trainer)
    probe = Probe(params0=_host(carry.params))
    for i in range(CHECK_CYCLES):
        carry, m = trainer.cycle(carry)
        probe.loss.append(np.asarray(jax.device_get(m)["loss"]))
        if i == 0:
            probe.moment = _host(_moment(carry.opt_state))
    probe.params3 = _host(carry.params)
    jax.block_until_ready(carry)
    return carry, probe


def reference_runs(cell: cells.Cell, seeds: List[int], devices,
                   dtype=None) -> List[Dict[str, Any]]:
    """The reference's first ``CHECK_CYCLES`` cycles for each seed, one
    replica per seed, vmapped and split over ``devices`` where there
    are several. The reference is the configuration's own, from the
    cell's checkout (``cells.load_reference``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    Reference = cells.load_reference(cell.config_name, cell.root)
    ref = Reference(cell.config, cell.envs,
                    dtype=jnp.float32 if dtype is None else dtype)
    init = jax.vmap(ref.init)
    step = jax.vmap(ref.cycle)
    n_dev = len(devices)
    if n_dev > 1 and len(seeds) % n_dev == 0:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("r",))
        spec = PartitionSpec("r")
        init = jax.shard_map(init, mesh=mesh, in_specs=spec, out_specs=spec,
                             check_vma=False)
        step = jax.shard_map(step, mesh=mesh, in_specs=spec, out_specs=spec,
                             check_vma=False)
        seed_arr = jax.device_put(jnp.asarray(seeds, jnp.int32),
                                  NamedSharding(mesh, spec))
    else:
        seed_arr = jax.device_put(jnp.asarray(seeds, jnp.int32), devices[0])
    init = jax.jit(init)
    step = jax.jit(step, donate_argnums=0)
    st = init(seed_arr)
    params0 = _host(st.params)
    losses = []
    moment = None
    for i in range(CHECK_CYCLES):
        st, loss = step(st)
        losses.append(np.asarray(jax.device_get(loss)))
        if i == 0:
            moment = _host(_moment(st.opt))
    params3 = _host(st.params)
    del st
    return [{"loss": np.asarray([l[r] for l in losses]),
             "moment": _replica(moment, r),
             "params0": _replica(params0, r),
             "params3": _replica(params3, r)} for r in range(len(seeds))]


def hbm_peak_bytes(device) -> int:
    """The most HBM the process has held on ``device``: the peak of its
    live buffers plus the peak the runtime reserved for the programs'
    temporaries, which a TPU keeps apart from the buffers and
    ``peak_bytes_in_use`` leaves out."""
    stats = device.memory_stats() or {}
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def cycle_times(ends: List[float]) -> Dict[str, Any]:
    """The window's cycles, each timed from the end of the one before to
    its metrics on the host: the least, median and largest, and the
    indices of those over 1.1 times the median (a stall)."""
    import numpy as np
    t = np.diff(np.asarray(ends))
    med = float(np.median(t))
    return {"min": float(t.min()), "median": med, "max": float(t.max()),
            "slow": [int(i) for i in np.flatnonzero(t > 1.1 * med)]}


def _trace_dir() -> str:
    return tempfile.mkdtemp(prefix="bench_trace_")


def _profile(trainer, carry, cycles: int):
    """Run ``cycles`` cycles under the profiler; returns the carry and
    the path of the trace (in a fresh directory under TMPDIR)."""
    import jax
    from jax.profiler import TraceAnnotation
    out = _trace_dir()
    jax.profiler.start_trace(out)
    try:
        with TraceAnnotation("bench.traced_window"):
            for _ in range(cycles):
                with TraceAnnotation("bench.cycle_dispatch"):
                    carry, m = trainer.cycle(carry)
                with TraceAnnotation("bench.metrics_fetch"):
                    jax.device_get(m)
            jax.block_until_ready(carry)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {out}")
    return carry, out, files[0]


def run(workload: str, seed: int, seconds: float, trace: bool,
        t0: Optional[float] = None, root: Path = cells.BENCH_DIR,
        require_tpu: bool = True,
        build: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of ``workload``; returns the result line as a dict.
    ``build`` replaces ``repro.api.build_trainer`` (the tests use it to
    plant faults in the timed path)."""
    t0 = time.perf_counter() if t0 is None else t0
    cell = cells.load_cell(workload, root)
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    devices = device_check(cell.chips, require_tpu)[:cell.chips]
    from repro import compile_cache
    from repro.api import ExperimentSpec, build_trainer
    from repro.envs import make_env
    if require_tpu:
        cache_dir = compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        log(f"[bench] compile cache {cache_dir}")
    counter = CompileCounter()
    build = build or build_trainer

    spec = ExperimentSpec.from_dict(cells.spec_dict(cell, seed))
    trainer = build(spec)
    P, C = spec.seeds, spec.schedule.cycle_steps
    n_actions = make_env(spec.env, **spec.env_params).n_actions
    carry, probe = first_cycles(trainer)
    setup_s = time.perf_counter() - t0
    set_up = counter.snapshot()
    log(f"[bench] {workload} seed {seed}: set-up {setup_s:.3f} s "
        f"(backend compile {set_up['backend_compile_s']:.3f} s in "
        f"{set_up['compiles']} programs, cache loads "
        f"{set_up['cache_retrieval_s']:.3f} s)")

    cycles, failed = 0, 0
    start = time.perf_counter()
    ends = [start]
    while True:
        with TraceAnnotation("bench.cycle_dispatch"):
            carry, m = trainer.cycle(carry)
        with TraceAnnotation("bench.metrics_fetch"):
            loss = np.asarray(jax.device_get(m)["loss"])
        ends.append(time.perf_counter())
        cycles += 1
        failed += int(np.sum(~np.isfinite(loss)))
        if ends[-1] - start >= seconds:
            break
    jax.block_until_ready(carry)
    window_s = time.perf_counter() - start
    rate = cycles * C * P / window_s
    in_window = counter.snapshot()["compiles"] - set_up["compiles"]
    cycle_s = cycle_times(ends)
    log(f"[bench] window: {cycles} cycles, {cycles * C * P} env steps in "
        f"{window_s:.3f} s = {rate:.1f} steps/s; {in_window} compiles in "
        "the window")
    log(f"[bench] cycle s: {cycle_s}")
    peak = max(hbm_peak_bytes(d) for d in devices)

    trace_path = trace_dir = None
    if trace:
        carry, trace_dir, trace_path = _profile(trainer, carry, TRACE_CYCLES)
    del carry, m, trainer

    seeds = [spec.seed + r for r in range(P)]
    ref_t = time.perf_counter()
    refs = reference_runs(cell, seeds, devices)
    per_replica = [compare.readings(probe.replica(r), refs[r])
                   for r in range(P)]
    got = compare.worst(per_replica)
    log(f"[bench] reference: {len(seeds)} replica(s), "
        f"{time.perf_counter() - ref_t:.3f} s")

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    bench = cells.benchmark(root.parent)
    result: Dict[str, Any] = {"correct": False, "attempted": cycles * P,
                              "failed": failed}
    if trace:
        metrics, breakdown, busy, win = _per_layer(
            cell, bench, root, trace_path, rate, set_up, d0.device_kind,
            n_actions)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=busy, window_s=win)
    else:
        values = {"env_steps_per_s": rate, "peak_hbm_gb": peak / 1e9,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cells.end_to_end_for(cell.name, bench)}
        breakdown = None

    # a number the configuration's limits file does not name is not
    # compared (bench/limits/<config>.json says why)
    limits = cell.limits.get("limits", {})
    checks = {k: {"value": got[k], "limit": limits[k]}
              for k in compare.NUMBERS if k in limits}
    correct = (failed == 0 and bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result.update(correct=correct, metrics=metrics, device=device,
                  cycle_s=cycle_s)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    log(f"correct {correct}")
    return result


def _per_layer(cell, bench, root, trace_path, rate, set_up, device_kind,
               n_actions):
    from bench import trace as tr
    from bench.counts import peaks

    t = tr.read(trace_path)
    ctx = {"trace": t, "cell": cell, "env_steps_per_s": rate,
           "chips": cell.chips, "peak": peaks(device_kind),
           "n_actions": n_actions, "compile": set_up}
    metrics = {}
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    for name in cells.per_layer_for(cell.name, bench):
        value = cells.load_reader(name, root)(ctx)
        if value is not None and math.isfinite(value):
            metrics[name] = {"value": value, "unit": units[name]}
    breakdown = {"device_ops": tr.op_totals(t), "idle_gaps": tr.idle_gaps(t)}
    return metrics, breakdown, tr.mean_busy_s(t), t.window_s
