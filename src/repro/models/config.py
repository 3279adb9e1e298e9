"""Model configuration for every assigned architecture family.

One :class:`ModelConfig` describes any of the ten architectures: dense GQA
transformers, sliding-window/local-attention variants, MoE (with optional
parallel dense residual, as in Arctic), RWKV6 (attention-free), and the
RG-LRU/local-attention hybrid (RecurrentGemma).  The layer stack is given
as a repeating ``pattern`` of layer kinds plus an optional remainder, which
keeps ``lax.scan``-over-layers applicable to heterogeneous stacks.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Optional, Tuple

import jax.numpy as jnp

# Layer kinds appearing in ``pattern``.
ATTN = "attn"  # (self-)attention block (full / windowed per config)
LOCAL = "local_attn"  # short-window local attention (RecurrentGemma)
RECURRENT = "recurrent"  # RG-LRU recurrent block
RWKV = "rwkv"  # RWKV6 time-mix + channel-mix block
LAYER_KINDS = (ATTN, LOCAL, RECURRENT, RWKV)


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    num_experts_per_tok: int = 2
    capacity_factor: float = 1.25
    # Arctic: a small dense FFN runs in parallel with the MoE ("dense residual")
    parallel_dense: bool = False
    # router implementation: "einsum" (GShard dispatch/combine einsums, robust
    # GSPMD sharding), "gather" (sort/gather based, true-FLOPs path), or
    # "held": the dropless layer of the ``held`` experts from ``first_held``
    # on, the share of an expert-parallel layer that one chip holds (the
    # router still scores all ``num_experts``; ``capacity_factor`` unused)
    impl: str = "einsum"
    router_aux_coef: float = 0.01
    held: Optional[int] = None  # "held" only; None holds every expert
    first_held: int = 0

    def __post_init__(self):
        if self.impl not in ("einsum", "gather", "held"):
            raise ValueError(f"unknown moe impl {self.impl!r}")
        if self.impl != "held" and (self.held is not None or self.first_held):
            raise ValueError("held and first_held belong to impl='held'")
        if not 0 <= self.first_held < self.first_held + self.num_held <= self.num_experts:
            raise ValueError("the held experts must lie within the router's num_experts")

    @property
    def num_held(self) -> int:
        """Experts whose weights this layer holds and computes."""
        return self.num_experts if self.held is None else self.held


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (Peng et al. 2023, arXiv 2309.00071), as the
    ``yarn`` rope type of Hugging Face transformers: frequencies ramped
    between interpolated (by ``factor``) and original ones over the
    dimensions that turn between ``beta_slow`` and ``beta_fast`` times in
    ``original_max_position_embeddings``, and cos/sin scaled by
    ``attention_factor`` (default ``0.1 ln(factor) + 1``).  ``kinds``: the
    layer kinds whose rotary it scales, as a source's per-layer-type rope
    parameters give them; the other kinds keep the default rotary."""

    kinds: Tuple[str, ...]
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def __post_init__(self):
        object.__setattr__(self, "kinds", tuple(self.kinds))
        if not self.kinds or not set(self.kinds) <= {ATTN, LOCAL}:
            raise ValueError(f"yarn kinds must name attention kinds, got {self.kinds!r}")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // num_heads

    # layer stack: ``pattern`` repeats; remainder layers appended at the end.
    # dense default: ("attn",) * 1 repeated num_layers times.
    pattern: Tuple[str, ...] = (ATTN,)

    # attention
    window: Optional[int] = None  # sliding window for ATTN (None = full)
    local_window: int = 2048  # window for LOCAL layers
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0  # chatglm applies rotary to half the head dim
    # YaRN on the rotary of the layer kinds in ``yarn.kinds`` (Mellum2: ATTN,
    # its full layers; its sliding LOCAL layers keep the default rotary)
    yarn: Optional[YarnConfig] = None
    attn_logit_softcap: Optional[float] = None
    # attention execution (models/attention.py): "auto" runs training and
    # prefill attention in JAX's Pallas splash kernel when compiled for a TPU
    # with a sequence a multiple of 128, else (and always in decode) the jnp
    # form below that fits: "chunked_kv" (KV-block scan, online softmax) when
    # Sq >= 2*attn_block and both lengths divide into blocks, else "naive"
    # (materialized (Sq, Sk) logits).  "naive", "chunked" (query-block scan,
    # O(block*Sk) memory) and "chunked_kv" force that jnp form everywhere.
    attn_impl: str = "auto"
    attn_block: int = 512

    # norms / activations
    # rmsnorm (its weight stored as gain - 1, Gemma's form) | rmsnorm_gain (the
    # gain itself, as Llama-family checkpoints store it) | layernorm |
    # nonparametric_ln (OLMo)
    norm: str = "rmsnorm"
    activation: str = "swiglu"  # swiglu | geglu | gelu | relu_sq
    parallel_block: bool = False  # attn+ffn in parallel (not used by defaults)
    tie_embeddings: bool = False
    use_bias_attn: bool = False  # starcoder2 / chatglm3 qkv bias
    use_bias_mlp: bool = False  # starcoder2

    # MoE: the FFN of every ATTN and LOCAL layer; a mapping of MoEConfig's
    # fields is accepted (as a configuration file gives it), as for ``yarn``
    moe: Optional[MoEConfig] = None

    # RWKV6 / RG-LRU
    rwkv_head_dim: int = 64
    d_rnn: Optional[int] = None  # RG-LRU recurrence width (defaults d_model)
    lru_block_width: Optional[int] = None

    # stub modality frontends (backbone-only per assignment):
    #   "none"  — token ids in, standard LM
    #   "patch" — precomputed patch embeddings prepended to token embeddings
    #   "frame" — precomputed frame embeddings in, projected to d_model
    frontend: str = "none"
    frontend_dim: int = 1024  # incoming embedding width for patch/frame stubs
    num_prefix_tokens: int = 256  # patch count for the vlm stub

    # numerics
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"
    logits_softcap: Optional[float] = None
    z_loss: float = 1e-4

    # training-time behaviour
    remat: str = "none"  # none | full | block | dots  — activation checkpoint policy
    scan_layers: bool = True

    def __post_init__(self):
        if isinstance(self.moe, Mapping):
            object.__setattr__(self, "moe", MoEConfig(**self.moe))
        if isinstance(self.yarn, Mapping):
            object.__setattr__(self, "yarn", YarnConfig(**self.yarn))
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.d_rnn is None:
            object.__setattr__(self, "d_rnn", self.d_model)
        for kind in self.pattern:
            if kind not in LAYER_KINDS:
                raise ValueError(f"unknown layer kind {kind!r}")
        if self.num_heads % self.num_kv_heads != 0:
            raise ValueError("num_heads must be divisible by num_kv_heads")

    # -- stack helpers -------------------------------------------------------

    @property
    def num_groups(self) -> int:
        """Number of full pattern repetitions."""
        return self.num_layers // len(self.pattern)

    @property
    def remainder(self) -> Tuple[str, ...]:
        """Layer kinds left over after the repeating groups."""
        r = self.num_layers % len(self.pattern)
        return self.pattern[:r]

    @property
    def attn_free(self) -> bool:
        return all(k in (RWKV, RECURRENT) for k in self.pattern)

    @property
    def subquadratic(self) -> bool:
        """Eligible for long_500k: every layer's state is o(seq_len)."""
        return all(
            k in (RWKV, RECURRENT, LOCAL) or (k == ATTN and self.window is not None)
            for k in self.pattern
        )

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    # -- size accounting ------------------------------------------------------

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + stacked layers + head)."""
        d, v = self.d_model, self.vocab_size
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d  # unembedding
        total += d  # final norm (rmsnorm scale); ok to count even if nonparam
        kinds = list(self.pattern) * self.num_groups + list(self.remainder)
        for kind in kinds:
            total += self._layer_params(kind)
        if self.frontend in ("patch", "frame"):
            total += self.frontend_dim * d  # stub projection
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts; of the experts
        held here, a token reaches ``held * k / num_experts`` on average)."""
        if self.moe is None:
            return self.param_count()
        moe = self.moe
        active = moe.num_held * moe.num_experts_per_tok / moe.num_experts
        inactive = round((moe.num_held - active) * self._ffn_expert_params())
        n_moe_layers = sum(
            1 for k in (list(self.pattern) * self.num_groups + list(self.remainder))
            if k in (ATTN, LOCAL)
        )
        return self.param_count() - inactive * n_moe_layers

    def _ffn_expert_params(self) -> int:
        d, f = self.d_model, self.d_ff
        return 3 * d * f if self.activation in ("swiglu", "geglu") else 2 * d * f

    def _layer_params(self, kind: str) -> int:
        d, f = self.d_model, self.d_ff
        hd = self.head_dim
        q = self.num_heads * hd
        kv = self.num_kv_heads * hd
        norms = 2 * d if self.norm != "nonparametric_ln" else 0
        if kind in (ATTN, LOCAL):
            attn = d * q + 2 * d * kv + q * d
            if self.moe is not None:
                ffn = self.moe.num_held * self._ffn_expert_params()
                ffn += d * self.moe.num_experts  # router
                if self.moe.parallel_dense:
                    ffn += self._ffn_expert_params()
            else:
                ffn = self._ffn_expert_params()
            return attn + ffn + norms
        if kind == RECURRENT:
            dr = self.d_rnn
            # RG-LRU block: in/out proj + conv1d(4) + gates a/x + ffn
            block = 2 * d * dr + 4 * dr + 2 * dr * dr // 8 + dr  # low-rank-ish gates
            return block + self._ffn_expert_params() + norms
        if kind == RWKV:
            # time-mix: r,k,v,g,o projections + decay/lora + channel-mix
            tm = 5 * d * d + 2 * d * 64 + 64 * d  # lora for data-dependent decay
            cm = 2 * d * int(f) if self.activation == "relu_sq" else 2 * d * f
            return tm + cm + norms
        raise ValueError(kind)
