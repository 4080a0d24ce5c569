"""Configuration system for the repro framework.

Every assigned architecture is described by a :class:`ModelConfig`; input
shapes by :class:`ShapeConfig`; the DQN reproduction by :class:`DQNConfig`.
Architectures register themselves in ``repro.configs`` and are selectable
via ``--arch <id>`` in every launcher.

Layer stacks are described as *superblocks* — a tuple of block kinds that
is repeated ``n_superblocks`` times and executed with ``lax.scan`` over the
repeats, so the lowered HLO size is independent of depth.

This module is the public configuration surface: import the dataclasses
below from ``repro.config``. (The historical re-export of ``ExecConfig``
from ``repro.models.layers`` is deprecated and warns — see that
module's ``__getattr__``.) The DQN variant family (``VariantConfig``)
is documented field-by-field in docs/variants.md; the declarative
experiment layer that composes these configs into one serializable run
description lives in ``repro.api`` (docs/experiment_api.md).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

__all__ = [
    "ExecConfig", "DEFAULT_EXEC", "MoEConfig", "SSMConfig", "XLSTMConfig",
    "ModelConfig", "ShapeConfig", "INPUT_SHAPES", "TrainConfig",
    "VariantConfig", "DQNConfig", "MeshConfig",
    "ATTN", "CROSS_ATTN", "MAMBA2", "MLSTM", "SLSTM", "BLOCK_KINDS",
    "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
]

@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Execution-strategy knobs, orthogonal to the architecture."""

    use_pallas: bool = False      # Pallas kernels for attention / SSM scan
    interpret: bool = False       # Pallas interpret mode (CPU validation)
    # kernel-backend request threaded to kernels/backend.py: "auto"
    # (platform pick: TPU->mosaic, GPU->triton, CPU->ref), "pallas",
    # "interpret", "ref", or a concrete backend name. The
    # REPRO_KERNEL_BACKEND env var overrides this at trace time.
    kernel_backend: str = "auto"
    compute_dtype: str = "bfloat16"
    remat: bool = False           # activation-checkpoint the superblock scan
    block_q: int = 512            # q-block for the blocked-XLA attention
    vocab_pad: int = 256          # pad vocab to a multiple (shardability)
    # MoE dispatch: "scatter" (capacity buffers, baseline), "expert_parallel"
    # (shard_map over the model axis, §Perf optimized) or "dense" (oracle)
    moe_impl: str = "scatter"
    fsdp: bool = False            # shard params/opt-state over the data axis
    # shard decode KV caches over the model axis along the sequence dim
    # (flash-decoding style partition; §Perf decode optimization)
    kv_seq_shard: bool = False
    # sLSTM scan unrolling: amortizes the recurrent-weight HBM reads over
    # k timesteps per loop iteration (§Perf xlstm iteration 2)
    slstm_unroll: int = 1
    # mLSTM formulation: chunkwise-parallel (optimized) vs per-token
    # recurrence (the paper-faithful baseline; §Perf xlstm iteration 1)
    mlstm_chunked: bool = True
    # decode attention: grouped GQA einsum (optimized) vs materialized
    # KV-repeat (baseline; §Perf decode iteration)
    decode_grouped: bool = True

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def kernel_request(self) -> str:
        """The logical backend request the kernel ops should dispatch on.

        ``use_pallas=True`` with the default ``kernel_backend='auto'``
        asks for the Pallas family ('pallas': native where the platform
        has one, interpret elsewhere); ``interpret=True`` narrows that to
        the interpreter. An explicit non-auto ``kernel_backend`` wins
        over both flags (and REPRO_KERNEL_BACKEND wins over everything,
        inside kernels/backend.py).
        """
        if self.kernel_backend != "auto":
            return self.kernel_backend
        return "interpret" if self.interpret else "pallas"


DEFAULT_EXEC = ExecConfig()


# ---------------------------------------------------------------------------
# Block kinds understood by repro.models.transformer
# ---------------------------------------------------------------------------
ATTN = "attn"            # causal self-attention (GQA) + MLP
CROSS_ATTN = "cross_attn"  # causal self-attn + cross-attn to memory + MLP
MAMBA2 = "mamba2"        # Mamba2 SSM block (no separate MLP)
MLSTM = "mlstm"          # xLSTM matrix-memory block
SLSTM = "slstm"          # xLSTM scalar-memory block
BLOCK_KINDS = (ATTN, CROSS_ATTN, MAMBA2, MLSTM, SLSTM)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts MLP configuration."""

    n_experts: int
    top_k: int
    n_shared_experts: int = 0   # always-active experts (qwen2-moe style)
    # deployment padding: expert weight stacks are padded to this count so
    # the `experts` axis divides the model-parallel mesh axis (e.g. 60 -> 64
    # for a 16-way axis). Routing stays n_experts-way; padded experts are
    # dead weight. 0 = no padding.
    pad_to: int = 0
    # capacity factor used by the dense-dispatch formulation (tokens kept
    # per expert = capacity_factor * tokens * top_k / n_experts); the
    # einsum dispatch used here is capacity-free but the field is kept for
    # the shard_map expert-parallel path.
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2-style state-space block configuration."""

    state_dim: int = 64          # N: per-channel state size
    expand: int = 2              # inner dim = expand * d_model
    head_dim: int = 64           # channels per SSM head
    conv_width: int = 4          # depthwise conv kernel size
    chunk: int = 128             # chunked-scan block length


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block configuration (arXiv:2405.04517)."""

    expand: int = 2              # mLSTM inner expansion
    conv_width: int = 4
    proj_factor_slstm: float = 4.0 / 3.0  # sLSTM post-FFN factor
    chunk: int = 64              # chunkwise-parallel mLSTM block length


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A full architecture description."""

    arch_id: str
    family: str                  # dense | moe | hybrid | vlm | ssm | audio
    citation: str

    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # layer stack: superblock repeated n_superblocks times
    superblock: Tuple[str, ...]
    n_superblocks: int

    head_dim: Optional[int] = None       # default d_model // n_heads
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None

    # encoder-decoder (whisper): a non-causal encoder stack feeding
    # cross-attention in the decoder superblocks.
    n_encoder_layers: int = 0
    encoder_seq: int = 0          # fixed encoder context (audio frames)

    # VLM: cross-attention memory provided by the (stubbed) vision tower.
    vision_tokens: int = 0        # patch-embedding sequence length

    # long-context decode: sliding-window KV ring buffer (sub-quadratic
    # variant used for the long_500k shape on full-attention archs).
    sliding_window: int = 4096

    # max positional extent advertised by the config (informational)
    max_context: int = 131_072

    mlp_kind: str = "swiglu"      # swiglu | gelu (whisper)
    pos_kind: str = "rope"        # rope | learned (whisper)
    learned_pos_len: int = 0      # table size when pos_kind == "learned"
    # zamba2-style weight sharing: a single attention block's parameters are
    # reused by every ATTN slot in the stack (cache stays per-invocation)
    shared_attention: bool = False

    @property
    def n_layers(self) -> int:
        return len(self.superblock) * self.n_superblocks

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def q_groups(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_encoder_decoder(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def has_cross_attention(self) -> bool:
        return CROSS_ATTN in self.superblock

    @property
    def cross_memory_len(self) -> int:
        if self.is_encoder_decoder:
            # conv frontend downsamples 2x in whisper
            return self.encoder_seq // 2
        return self.vision_tokens

    @property
    def attention_free(self) -> bool:
        return not any(k in (ATTN, CROSS_ATTN) for k in self.superblock)

    def validate(self) -> None:
        assert self.family in ("dense", "moe", "hybrid", "vlm", "ssm", "audio"), self.family
        assert all(k in BLOCK_KINDS for k in self.superblock), self.superblock
        assert self.n_heads % self.n_kv_heads == 0
        if self.moe is not None:
            assert self.moe.top_k <= self.moe.n_experts
        if MAMBA2 in self.superblock:
            assert self.ssm is not None
        if MLSTM in self.superblock or SLSTM in self.superblock:
            assert self.xlstm is not None
        if CROSS_ATTN in self.superblock:
            assert self.cross_memory_len > 0


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """An assigned (input-shape) workload."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Optimizer / step configuration for the LLM training path."""

    learning_rate: float = 3e-4
    warmup_steps: int = 100
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True            # activation checkpointing over the layer scan
    microbatch: int = 0           # 0 = no gradient accumulation


@dataclasses.dataclass(frozen=True)
class VariantConfig:
    """Off-policy DQN variant family, orthogonal to the execution strategy.

    The paper closes arguing its framework "should be generalizable to a
    large number of off-policy deep reinforcement learning methods";
    this config is that family: double Q-learning (van Hasselt et al.
    2016), dueling heads (Wang et al. 2016), proportional prioritized
    replay (Schaul et al. 2016), n-step returns (Sutton 1988), C51
    distributional value learning (Bellemare et al. 2017) and NoisyNet
    exploration (Fortunato et al. 2018) — each independently toggleable
    and all composable; ``rainbow`` composes all six (Hessel et al.
    2018). Defaults reproduce vanilla uniform-replay DQN exactly.
    Field semantics and per-preset values are tabulated in
    docs/variants.md (the authoritative variant matrix).
    """

    name: str = "dqn"
    # double: bootstrap Q_θ⁻(s', argmax_a Q_θ(s', a)) instead of
    # max_a Q_θ⁻(s', a)
    double: bool = False
    # dueling: V + (A - mean A) head split in the Nature CNN
    dueling: bool = False
    # prioritized: proportional PER sampled through the segment_tree op;
    # priorities stage during the cycle, flush at the θ⁻ ← θ sync point
    prioritized: bool = False
    # n_step: n-step return accumulation on the staging buffer; the loss
    # bootstraps with γⁿ
    n_step: int = 1
    per_alpha: float = 0.6        # priority exponent (Schaul et al. Table 3)
    per_beta0: float = 0.4        # initial IS-correction exponent
    per_beta_anneal_steps: int = 1_000_000   # beta -> 1 over this horizon
                                             # (0: beta stays per_beta0)
    per_eps: float = 1e-3         # additive mass so td=0 stays sampleable
    # distributional: C51 categorical value head (num_atoms × actions
    # logits), cross-entropy loss against the categorical_projection of
    # the target distribution; PER priorities come from the per-sample
    # cross-entropy (the KL term + a θ-independent entropy offset)
    distributional: bool = False
    num_atoms: int = 51           # K: support resolution (51 = "C51")
    v_min: float = -10.0          # support lower edge z_0
    v_max: float = 10.0           # support upper edge z_{K-1}
    # noisy: factorized-Gaussian NoisyNet linear layers in place of the
    # post-conv linears; ε-greedy is disabled (ε=0) and exploration
    # comes from per-cycle noise resampled off the cycle RNG, keeping
    # the bitwise-determinism guarantee
    noisy: bool = False
    noisy_sigma0: float = 0.5     # σ-parameter init scale σ0/√fan_in
    # recurrent (R2D2, Kapturowski et al. 2019): a sequence_length > 0
    # replays sequences of burn_in + sequence_length steps (+1 to
    # bootstrap the last) that start every sequence_period steps of a
    # stream, with the recurrent state stored at each start; the first
    # burn_in steps are unrolled without gradient. 0 = transitions.
    sequence_length: int = 0
    burn_in: int = 0
    sequence_period: int = 0
    # sequence priority η·max|δ| + (1 - η)·mean|δ| over its trained steps
    priority_eta: float = 0.9
    # value rescaling h(x) = sign(x)(√(|x|+1) - 1) + εx of the targets
    # (Pohlen et al. 2018); > 0 when recurrent
    value_rescale_eps: float = 0.0
    # Ape-X per-stream exploration ε_i = actor_eps^(1 + 7·i/(W-1)) (Horgan
    # et al. 2018); > 0 when recurrent
    actor_eps: float = 0.0

    @property
    def recurrent(self) -> bool:
        return self.sequence_length > 0

    def validate(self) -> None:
        assert self.n_step >= 1, self.n_step
        assert 0.0 <= self.per_alpha <= 1.0, self.per_alpha
        assert 0.0 <= self.per_beta0 <= 1.0, self.per_beta0
        assert self.num_atoms >= 1, self.num_atoms
        assert self.v_max >= self.v_min, (self.v_min, self.v_max)
        if self.distributional:
            assert self.num_atoms >= 2, "C51 needs a non-degenerate support"
        assert self.noisy_sigma0 >= 0.0, self.noisy_sigma0
        if self.recurrent:
            assert self.prioritized and self.double and not (
                self.distributional or self.noisy), \
                "sequence replay is prioritized double Q, with a scalar, " \
                "noise-free head"
            assert self.sequence_period >= 1 and self.burn_in >= 1, (
                self.sequence_period, self.burn_in)
            assert 0.0 <= self.priority_eta <= 1.0, self.priority_eta
            assert self.value_rescale_eps > 0.0, self.value_rescale_eps
            assert 0.0 < self.actor_eps <= 1.0, self.actor_eps


@dataclasses.dataclass(frozen=True)
class DQNConfig:
    """Paper hyperparameters (Mnih et al. 2015 / Table 5 of the paper)."""

    minibatch_size: int = 32
    replay_capacity: int = 1_000_000
    target_update_period: int = 10_000   # C
    train_period: int = 4                # F
    discount: float = 0.99
    prepopulate: int = 50_000            # N
    learning_rate: float = 2.5e-4
    rmsprop_decay: float = 0.95
    rmsprop_eps: float = 0.01
    rmsprop_centered: bool = True
    eps_start: float = 1.0
    eps_end: float = 0.1
    eps_anneal_steps: int = 1_000_000
    eval_eps: float = 0.05
    n_envs: int = 8                      # W sampler "threads"
    frame_stack: int = 4
    concurrent: bool = True              # Concurrent Training enabled
    synchronized: bool = True            # Synchronized Execution enabled
    variant: VariantConfig = VariantConfig()   # off-policy variant family
    # cycles between θ⁻ ← θ syncs, in a recurrent variant's cycle, which
    # keeps θ⁻ in the carry and acts from θ at the boundary; the
    # feed-forward cycle syncs θ⁻ at every boundary and acts from it
    target_cycles: int = 1

    @property
    def updates_per_cycle(self) -> int:
        return self.target_update_period // self.train_period  # C / F


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Production mesh description."""

    multi_pod: bool = False

    @property
    def shape(self) -> Tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")
