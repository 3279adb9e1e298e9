"""mellum2-12b-a2.5b [moe] — sliding-window and full GQA layers, 64 experts.

28L d_model=2304 32H (GQA kv=4) head_dim=128 experts 64 x 896 top-8
vocab=98304 untied [huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
config.json]

Every layer's FFN is sparse: a 64-way softmax router, the top 8 with their
weights renormalised (``norm_topk_prob``), SwiGLU experts of 896.  Layers
repeat in periods of four: three attend over a sliding window of 1024 with
the default rotary, the fourth over the whole context with YaRN (factor 16
over 8192 original positions); rotary theta 500,000 in both.  RMSNorm eps
1e-6, its weight the gain itself (``rmsnorm_gain``: the Llama-family form,
initialised to ones; the config names no offset), no attention bias, no
q/k norm.  The config gives no balance-loss coefficient: 0.001 is assumed
(Qwen-MoE's).
"""

import dataclasses

from repro.models.config import LOCAL, ATTN, ModelConfig, MoEConfig, YarnConfig

ARCH_ID = "mellum2-12b-a2.5b"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        family="moe",
        num_layers=28,
        d_model=2304,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        d_ff=896,  # moe_intermediate_size (every layer is sparse)
        vocab_size=98304,
        pattern=(LOCAL, LOCAL, LOCAL, ATTN),
        local_window=1024,
        rope_theta=500_000.0,
        yarn=YarnConfig(
            kinds=(ATTN,), factor=16.0, original_max_position_embeddings=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782,
        ),
        activation="swiglu",
        norm="rmsnorm_gain",
        tie_embeddings=False,
        moe=MoEConfig(num_experts=64, num_experts_per_tok=8, impl="held", router_aux_coef=0.001),
        # per block: a backward holds one layer's residuals; a whole group's
        # took 10.0 GB of temporaries against 4.9 (stage, 2 x 8192, v5e)
        remat="block",
    )


def stage_config() -> ModelConfig:
    """One chip of an expert-parallel deployment: 8 chips share each layer
    (8 of the 64 experts and 12,288 of the vocabulary's rows each), and 7
    pipeline stages hold 4 layers each; this chip holds stage 0's first
    share: layers 0-3, experts 0-7, the first 12,288 rows."""
    full = config()
    return dataclasses.replace(
        full, name=ARCH_ID + "-l4e8", num_layers=4, vocab_size=12288,
        moe=dataclasses.replace(full.moe, held=8, first_held=0),
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke",
        family="moe",
        num_layers=4,
        d_model=64,
        num_heads=8,
        num_kv_heads=2,
        head_dim=16,
        d_ff=32,
        vocab_size=256,
        pattern=(LOCAL, LOCAL, LOCAL, ATTN),
        local_window=8,
        rope_theta=500_000.0,
        yarn=YarnConfig(kinds=(ATTN,), factor=4.0, original_max_position_embeddings=8),
        activation="swiglu",
        norm="rmsnorm_gain",
        moe=MoEConfig(num_experts=8, num_experts_per_tok=2, impl="held", router_aux_coef=0.001),
        dtype="float32",
    )
