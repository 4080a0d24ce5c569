"""`ExperimentSpec` — one declarative, serializable run description.

The paper's contribution is an *execution framework* (baseline →
synchronized → concurrent), and every experiment in this repo is a
point in the same grid: an environment, an off-policy variant, a
schedule, an execution mode, a population size, and the execution-
strategy knobs (`ExecConfig`). `ExperimentSpec` is that point as a
frozen dataclass with a **lossless JSON round-trip** — commit the file
`spec.to_json()` writes and the run is reproducible from it alone
(`rl_train --spec run.json`). `repro.api.build_trainer(spec)` is the
single construction path from a spec to a running `Trainer`
(see `repro.api.trainers`); docs/experiment_api.md documents the
schema field by field.

Round-trip contract (enforced by tests/test_api.py and the CI golden-
spec job over examples/specs/):

* ``ExperimentSpec.from_json(spec.to_json()) == spec`` for every spec;
* ``to_json`` is canonical — sorted keys, 2-space indent, every field
  present, trailing newline — so ``from_json(text).to_json() == text``
  byte-for-byte whenever ``text`` was produced by ``to_json``.

The spec deliberately stores *launcher-level* knobs and derives the
runtime configs (`DQNConfig`, `NatureCNNConfig`) through
:meth:`ExperimentSpec.dqn_config` / :meth:`ExperimentSpec.cnn_config`,
so a spec cannot hold two contradictory copies of the same fact
(e.g. ``cycle_steps`` vs ``target_update_period``).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

from repro.config import DQNConfig, ExecConfig, VariantConfig

__all__ = [
    "MODES", "ScheduleSpec", "AlgoSpec", "CheckpointSpec", "MetricsSpec",
    "ExperimentSpec", "SpecCompatError", "spec_compat_diff",
    "check_resume_compat", "save_run_spec", "load_run_spec",
    "RUN_SPEC_FILENAME",
]

# Execution modes understood by the trainer registry
# (repro.api.trainers.TRAINERS registers exactly these; the pairing is
# asserted by tests/test_api.py so the two cannot drift).
MODES = ("baseline", "synchronized", "concurrent", "population")

# File written beside the checkpoints so --resume can validate that the
# requested spec still describes the run that produced the carry.
RUN_SPEC_FILENAME = "spec.json"


@dataclasses.dataclass(frozen=True)
class ScheduleSpec:
    """How long to run and how often to evaluate."""

    cycles: int = 60          # outer loop length (one C-cycle per entry)
    cycle_steps: int = 256    # C: env steps per cycle (= θ⁻ sync period)
    prepopulate: int = 2048   # N: uniform-random transitions seeding 𝒟
    eval_every: int = 20      # cycles between ε=0.05 evaluations
    eval_episodes: int = 64   # parallel evaluation streams per eval
    # cycles between θ⁻ ← θ syncs. 1: θ⁻ is synced at every cycle
    # boundary (the paper's C-cycle). A recurrent variant's boundary
    # refreshes the acting parameters (R2D2's actor refresh) and θ⁻
    # follows every K cycles (R2D2's target period, in updates, is
    # K × cycle_steps / train_period); only it may set K > 1.
    target_cycles: int = 1


@dataclasses.dataclass(frozen=True)
class AlgoSpec:
    """DQN hyperparameters not implied by the schedule."""

    minibatch_size: int = 32
    replay_capacity: int = 16384
    train_period: int = 2     # F: env steps per gradient update
    discount: float = 0.9
    # "adamw" (fast convergence on the JAX envs, the launcher default)
    # or "rmsprop" (Mnih's centered RMSProp — paper-faithful, tuned for
    # 200M-frame Atari budgets; rl_train --paper-optimizer).
    optimizer: str = "adamw"
    learning_rate: float = 0.0   # 0.0 = the optimizer's default
                                 # (adamw 1e-3, rmsprop 2.5e-4)
    eps_anneal_steps: int = 0    # 0 = derive cycles * cycle_steps // 2
    # Adam ("adamw") only: second-moment decay, ε in the denominator and
    # the global-norm gradient clip (0.0 = no clip)
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    adam_grad_clip: float = 1.0


@dataclasses.dataclass(frozen=True)
class CheckpointSpec:
    """Where/how often the full carry checkpoints (dir=None: never)."""

    dir: Optional[str] = None
    every: int = 20           # cycles between checkpoints


@dataclasses.dataclass(frozen=True)
class MetricsSpec:
    """Per-(cycle, replica) metrics sink (jsonl=None: stdout only)."""

    jsonl: Optional[str] = None


def _default_exec() -> ExecConfig:
    # The DQN reproduction trains in full precision (paper default);
    # the LLM-path ExecConfig defaults to bf16, so pin f32 here.
    return ExecConfig(compute_dtype="float32", kernel_backend="auto")


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One declarative experiment: env × variant × schedule × mode ×
    population × execution knobs. See the module docstring for the
    JSON round-trip contract and docs/experiment_api.md for the schema.
    """

    env: str = "catch"            # envs/games.py registry name
    # Static EnvParams overrides for the env (envs/games.py dataclasses):
    # e.g. {"size": 16, "paddle_width": 5}. {} = the game's defaults.
    env_params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    mode: str = "population"      # one of MODES
    variant: VariantConfig = VariantConfig()
    envs: int = 8                 # W sampler streams
    # What one observation is: "pixels" (rendered uint8 frames, the
    # paper's pipeline) or "vector" (EnvSpec.observe state vectors, the
    # deep_q_rl machine-state lineage).
    obs_mode: str = "pixels"
    frame_size: int = 10          # 10 (MinAtar grids) or 84 (Nature geometry)
    # Q-network (api/nets.NETS): a Nature-CNN geometry preset
    # (configs/dqn_nature.cnn_geometry): "auto" = frame_size pick
    # (10 -> "small", 84 -> "nature") or, under obs_mode="vector", the
    # fc-only "mlp"; "tiny"/"mlp_tiny" are the dryrun/tests networks;
    # or "r2d2", the recurrent network of a sequence-replay variant.
    net: str = "auto"
    seed: int = 0                 # base replica seed (replica r: seed + r)
    seeds: int = 1                # population size P (population mode)
    schedule: ScheduleSpec = ScheduleSpec()
    algo: AlgoSpec = AlgoSpec()
    checkpoint: CheckpointSpec = CheckpointSpec()
    metrics: MetricsSpec = MetricsSpec()
    exec: ExecConfig = dataclasses.field(default_factory=_default_exec)

    # -- validation --------------------------------------------------------

    def validate(self) -> None:
        from repro.api.nets import check_net
        from repro.envs import make_env
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r}; one of {MODES}")
        # unknown games / unknown param names / out-of-range values all
        # raise ValueError messages listing what IS valid (games.make_env)
        env = make_env(self.env, **self.env_params)
        if self.obs_mode not in ("pixels", "vector"):
            raise ValueError(
                f"unknown obs_mode {self.obs_mode!r}; one of "
                "('pixels', 'vector')")
        check_net(self)
        mlp_net = self.net in ("mlp", "mlp_tiny")
        if self.obs_mode == "vector" and not (mlp_net or self.net == "auto"):
            raise ValueError(
                f"obs_mode='vector' feeds flat state vectors; net "
                f"{self.net!r} is a conv preset — use net='auto', 'mlp' "
                "or 'mlp_tiny'")
        if self.obs_mode == "pixels" and mlp_net:
            raise ValueError(
                f"net {self.net!r} consumes vector observations; set "
                "obs_mode='vector' (or pick a conv preset)")
        if self.obs_mode == "pixels":
            if self.net == "auto" and self.frame_size not in (10, 84):
                raise ValueError(
                    f"net='auto' resolves on frame_size 10 or 84, got "
                    f"{self.frame_size}; pick an explicit net preset")
            if self.frame_size == 84 and env.size != 10:
                raise ValueError(
                    f"frame_size=84 assumes a 10x10 grid (8x upscale); "
                    f"env {self.env!r} with size={env.size} renders "
                    f"natively — set frame_size={env.size}")
            if self.frame_size not in (84, env.size):
                raise ValueError(
                    f"frame_size={self.frame_size} matches neither the "
                    f"env grid (size={env.size}) nor the 84x84 Nature "
                    "geometry")
        if self.algo.optimizer not in ("adamw", "rmsprop"):
            raise ValueError(
                f"unknown optimizer {self.algo.optimizer!r}; "
                "one of ('adamw', 'rmsprop')")
        for name, v in (("envs", self.envs), ("seeds", self.seeds),
                        ("cycles", self.schedule.cycles),
                        ("cycle_steps", self.schedule.cycle_steps),
                        ("minibatch_size", self.algo.minibatch_size),
                        ("replay_capacity", self.algo.replay_capacity),
                        ("train_period", self.algo.train_period),
                        ("target_cycles", self.schedule.target_cycles)):
            if v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
        # the driver loop computes `(cycle + 1) % cadence` — a 0 cadence
        # is a ZeroDivisionError deep inside training, so reject it here
        # with the intent spelled out
        for name, v in (("schedule.eval_every", self.schedule.eval_every),
                        ("schedule.eval_episodes",
                         self.schedule.eval_episodes),
                        ("checkpoint.every", self.checkpoint.every)):
            if v < 1:
                raise ValueError(
                    f"{name} must be >= 1, got {v} (the driver fires on "
                    f"`cycle % {name.split('.')[-1]} == 0` and always "
                    "runs the final cycle; for final-cycle-only "
                    f"behaviour set {name} = schedule.cycles)")
        self.variant.validate()
        if self.variant.recurrent:
            self._validate_sequences()
        elif self.schedule.target_cycles != 1:
            raise ValueError(
                f"schedule.target_cycles={self.schedule.target_cycles} "
                "needs a recurrent variant: the feed-forward cycle syncs "
                "θ⁻ at every boundary")

    def _validate_sequences(self) -> None:
        """The sequence replay's geometry: each stream's ring holds a
        whole number of sequence periods, and cycles and prepopulation
        end on a period boundary."""
        v, W = self.variant, self.envs
        period = v.sequence_period
        if self.algo.replay_capacity % W:
            raise ValueError(
                f"replay_capacity counts sequences, a whole number per "
                f"stream: {self.algo.replay_capacity} % envs {W} != 0")
        for name, steps in (("schedule.cycle_steps",
                             self.schedule.cycle_steps),
                            ("schedule.prepopulate",
                             self.schedule.prepopulate)):
            if steps % (W * period):
                raise ValueError(
                    f"{name}={steps} must be a multiple of envs x "
                    f"sequence_period = {W * period}")
        span = v.burn_in + v.sequence_length + 1
        if self.schedule.prepopulate // W < span:
            raise ValueError(
                f"prepopulate gives each stream "
                f"{self.schedule.prepopulate // W} steps; one sequence "
                f"spans {span}")
        # a sequence also reads the frame_stack - 1 frames before it,
        # from the period before its start
        from repro.api.nets import frame_stack
        ring = self.algo.replay_capacity // W * period
        if (period < frame_stack(self) - 1
                or ring < period * (-(-span // period) + 1)):
            raise ValueError(
                f"a stream's ring of {ring} steps ({period}-step periods) "
                f"holds no whole sequence of {span} steps with the "
                "period before it")

    # -- derived runtime configs ------------------------------------------

    def obs_dim(self) -> int:
        """The env's vector-observation width under obs_mode='vector',
        else 0 (pixel mode)."""
        if self.obs_mode != "vector":
            return 0
        from repro.envs import make_env
        return make_env(self.env, **self.env_params).obs_dim

    def cnn_config(self, n_actions: int):
        """The ``NatureCNNConfig`` this spec implies (geometry preset +
        the variant's head selection)."""
        from repro.configs.dqn_nature import cnn_config_for, cnn_geometry
        base = cnn_geometry(self.net, self.frame_size, n_actions,
                            obs_dim=self.obs_dim())
        return cnn_config_for(self.variant, base)

    def dqn_config(self) -> DQNConfig:
        """The ``DQNConfig`` this spec implies. ``target_update_period``
        IS the cycle length (the C-cycle definition) and the ε anneal
        horizon defaults to half the run."""
        sched, algo = self.schedule, self.algo
        eps_anneal = algo.eps_anneal_steps or max(
            sched.cycles * sched.cycle_steps // 2, 1)
        from repro.api.nets import frame_stack
        return DQNConfig(
            minibatch_size=algo.minibatch_size,
            replay_capacity=algo.replay_capacity,
            target_update_period=sched.cycle_steps,
            train_period=algo.train_period,
            prepopulate=sched.prepopulate,
            n_envs=self.envs,
            frame_stack=frame_stack(self),
            eps_anneal_steps=eps_anneal,
            discount=algo.discount,
            concurrent=self.mode in ("concurrent", "population"),
            synchronized=self.mode != "baseline",
            variant=self.variant,
            target_cycles=sched.target_cycles)

    # -- construction helpers ---------------------------------------------

    @classmethod
    def from_preset(cls, variant: str, **overrides) -> "ExperimentSpec":
        """A spec for a named variant preset (configs/dqn_nature.VARIANTS);
        ``overrides`` are regular field overrides."""
        from repro.configs.dqn_nature import get_variant
        return cls(variant=get_variant(variant), **overrides)

    def replace(self, **changes) -> "ExperimentSpec":
        return dataclasses.replace(self, **changes)

    # -- JSON round-trip ---------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        """Canonical JSON: sorted keys, 2-space indent, every field
        present, trailing newline. ``from_json(s.to_json()) == s`` and
        re-serialization is byte-identical."""
        return json.dumps(self.to_dict(), indent=2, sort_keys=True) + "\n"

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "ExperimentSpec":
        return _build_dataclass(cls, data, path="")

    @classmethod
    def from_json(cls, text: str) -> "ExperimentSpec":
        data = json.loads(text)
        if not isinstance(data, dict):
            raise ValueError(
                f"spec JSON must be an object, got {type(data).__name__}")
        return cls.from_dict(data)


# Nested dataclass field types, kept explicit (the class annotations are
# strings under `from __future__ import annotations`).
_NESTED = {
    "variant": VariantConfig,
    "schedule": ScheduleSpec,
    "algo": AlgoSpec,
    "checkpoint": CheckpointSpec,
    "metrics": MetricsSpec,
    "exec": ExecConfig,
}


def _build_dataclass(dc_type, data: Dict[str, Any], path: str):
    """Reconstruct a (possibly nested) frozen dataclass from a JSON
    dict. Unknown keys are an error (typos must not silently become
    defaults); missing keys fall back to the field defaults (older spec
    files keep loading after the schema grows). Ints given for float
    fields are coerced so the canonical serialization stays stable."""
    if not isinstance(data, dict):
        raise ValueError(f"spec field {path or '<root>'}: expected an "
                         f"object, got {type(data).__name__}")
    fields = {f.name: f for f in dataclasses.fields(dc_type)}
    unknown = sorted(set(data) - set(fields))
    if unknown:
        raise ValueError(
            f"unknown spec field(s) {', '.join(path + k for k in unknown)} "
            f"for {dc_type.__name__}; known: {sorted(fields)}")
    kwargs: Dict[str, Any] = {}
    for name, val in data.items():
        sub = _NESTED.get(name) if dc_type is ExperimentSpec else None
        if sub is not None:
            kwargs[name] = _build_dataclass(sub, val, f"{path}{name}.")
            continue
        default = fields[name].default
        if isinstance(default, bool):
            if not isinstance(val, bool):
                raise ValueError(f"spec field {path}{name}: expected a "
                                 f"bool, got {val!r}")
        elif isinstance(default, float) and isinstance(val, int) \
                and not isinstance(val, bool):
            val = float(val)
        kwargs[name] = val
    try:
        return dc_type(**kwargs)
    except TypeError as e:
        raise ValueError(f"invalid spec at {path or '<root>'}: {e}") from None


# ---------------------------------------------------------------------------
# Resume compatibility: the spec is stored beside the checkpoints, and a
# mismatched --resume fails with a field-level diff instead of an opaque
# unflatten/shape error deep inside the checkpoint restore.
# ---------------------------------------------------------------------------

class SpecCompatError(ValueError):
    """Raised when a resume request's spec does not describe the run
    that produced the stored checkpoints."""


# Fields that may differ between the stored and the requested spec
# without invalidating the carry: output paths, and schedule knobs that
# only extend or re-time the run (resuming with more cycles or a
# different eval cadence is the normal way to continue a run).
_COMPAT_EXEMPT = {
    "checkpoint": None,                     # whole section
    "metrics": None,                        # whole section
    "schedule": {"cycles", "eval_every", "eval_episodes"},
}


def _compat_view(spec: ExperimentSpec) -> Dict[str, Any]:
    d = spec.to_dict()
    # Materialize derived fields BEFORE dropping the exempt schedule
    # knobs: eps_anneal_steps=0 derives from cycles, so extending a run
    # whose anneal horizon is derived would silently change the ε
    # schedule the guard exists to protect — the materialized value
    # makes that show up as an algo.eps_anneal_steps diff (pin
    # eps_anneal_steps explicitly to make a run extendable).
    if d["algo"]["eps_anneal_steps"] == 0:
        d["algo"]["eps_anneal_steps"] = max(
            d["schedule"]["cycles"] * d["schedule"]["cycle_steps"] // 2, 1)
    for key, sub in _COMPAT_EXEMPT.items():
        if sub is None:
            d.pop(key, None)
        else:
            d[key] = {k: v for k, v in d[key].items() if k not in sub}
    return d


def spec_compat_diff(stored: ExperimentSpec,
                     requested: ExperimentSpec) -> List[str]:
    """Field-level differences that make ``requested`` incompatible
    with the run ``stored`` describes. Empty list = compatible."""
    diffs: List[str] = []

    def walk(a: Any, b: Any, path: str):
        if isinstance(a, dict) and isinstance(b, dict):
            for k in sorted(set(a) | set(b)):
                walk(a.get(k), b.get(k), f"{path}.{k}" if path else k)
            return
        if a != b:
            diffs.append(f"{path}: checkpoint={a!r}, requested={b!r}")

    walk(_compat_view(stored), _compat_view(requested), "")
    return diffs


def check_resume_compat(stored: ExperimentSpec,
                        requested: ExperimentSpec) -> None:
    """Raise :class:`SpecCompatError` (with the field-level diff in the
    message) when ``requested`` cannot resume ``stored``'s carry."""
    diffs = spec_compat_diff(stored, requested)
    if diffs:
        raise SpecCompatError(
            "resume spec does not match the checkpointed run "
            f"({len(diffs)} field(s) differ):\n  " + "\n  ".join(diffs)
            + "\n(the stored spec lives in the checkpoint dir as "
            f"{RUN_SPEC_FILENAME}; pass a matching --spec/flags, or "
            "point --ckpt-dir at a fresh directory)")


def save_run_spec(ckpt_dir: str, spec: ExperimentSpec) -> str:
    """Write the resolved spec beside the checkpoints (canonical JSON).
    An existing compatible spec file is left untouched so resumed runs
    keep the original file's mtime/provenance. An *incompatible* stored
    spec that still has checkpoints beside it refuses to be overwritten:
    silently replacing it would let a later --resume restore the old
    run's carry under the new run's description."""
    os.makedirs(ckpt_dir, exist_ok=True)
    path = os.path.join(ckpt_dir, RUN_SPEC_FILENAME)
    if os.path.exists(path):
        stored = load_run_spec(ckpt_dir)
        if stored is not None and not spec_compat_diff(stored, spec):
            return path
        has_ckpts = any(f.startswith("step_") and f.endswith(".npz")
                        for f in os.listdir(ckpt_dir))
        if stored is not None and has_ckpts:
            raise SpecCompatError(
                f"{ckpt_dir} already holds checkpoints from a run with a "
                "different spec:\n  "
                + "\n  ".join(spec_compat_diff(stored, spec))
                + "\npoint --ckpt-dir at a fresh directory (or delete the "
                "old run's step_*.npz + spec.json to reuse this one)")
    # atomic write (tmp + rename), like the checkpoints themselves — a
    # run killed mid-write must not leave a truncated spec.json
    import tempfile
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        f.write(spec.to_json())
    os.replace(tmp, path)
    return path


def load_run_spec(ckpt_dir: str) -> Optional[ExperimentSpec]:
    """The spec stored beside the checkpoints, or None when absent
    (pre-API checkpoint dirs). An unreadable/corrupt file raises
    :class:`SpecCompatError` naming the path, so launchers surface one
    actionable message instead of a raw JSON traceback."""
    path = os.path.join(ckpt_dir, RUN_SPEC_FILENAME)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        text = f.read()
    try:
        return ExperimentSpec.from_json(text)
    except ValueError as e:
        raise SpecCompatError(
            f"stored run spec {path} is unreadable ({e}); delete it (and "
            "the step_*.npz checkpoints, if the run is dead) or restore "
            "it from the original --print-spec output") from None
