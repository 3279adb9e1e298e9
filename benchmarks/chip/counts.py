"""Operations, bytes and peaks, computed from shapes alone.

Everything here is a pure function of a configuration file's ``model``
block and a traffic file's sizes; nothing is read from the program.  The
per-layer metrics divide these counts by times taken from the host clock
or the device trace.

Conventions:

* Training FLOPs per token are ``6 N + 12 L D T`` (PaLM, Chowdhery et al.
  2022, appendix B), with ``N`` every parameter of the model once, so a
  tied unembedding counts as the matmul it is.  Recomputation under remat
  is not counted.
* Forward FLOPs per token are a third of that: ``2 N_body + 4 L D T``.
* Decode bytes per step are the weights at the configuration's compute
  ``dtype`` (every step casts the stored weights to it and computes with
  it), plus the keys and values of the positions attended so far.
"""

from __future__ import annotations

from typing import Dict, Mapping

#: Published peaks per chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
    },
}

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def _layer_params(m: Mapping) -> int:
    d, f = m["d_model"], m["d_ff"]
    hd = m.get("head_dim") or d // m["num_heads"]
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    if m.get("use_bias_attn"):
        attn += q + 2 * kv + d
    glu = m["activation"] in ("swiglu", "geglu")
    ffn = (3 if glu else 2) * d * f
    if m.get("use_bias_mlp"):
        ffn += f + d
    norms = 0 if m["norm"] == "nonparametric_ln" else 2 * _norm_params(m)
    return attn + ffn + norms


def _norm_params(m: Mapping) -> int:
    d = m["d_model"]
    return {"layernorm": 2 * d, "rmsnorm": d, "nonparametric_ln": 0}[m["norm"]]


def param_count(m: Mapping) -> int:
    """Every parameter of the model once (a tied unembedding is the embedding)."""
    d, v = m["d_model"], m["vocab_size"]
    n = v * d + _norm_params(m) + m["num_layers"] * _layer_params(m)
    if not m.get("tie_embeddings"):
        n += v * d
    return n


def body_params(m: Mapping) -> int:
    """Parameters a token's forward pass multiplies by, bar the LM head."""
    return param_count(m) - m["vocab_size"] * m["d_model"] * (
        1 if m.get("tie_embeddings") else 2
    )


def train_flops_per_token(m: Mapping, seq_len: int) -> float:
    return 6.0 * param_count(m) + 12.0 * m["num_layers"] * m["d_model"] * seq_len


def prefill_flops(m: Mapping, batch: int, prompt_len: int) -> float:
    """One prefill: every prompt position through the layers, LM head on the last."""
    layers = 2.0 * body_params(m) + 4.0 * m["num_layers"] * m["d_model"] * prompt_len
    head = 2.0 * m["d_model"] * m["vocab_size"]
    return batch * (prompt_len * layers + head)


def mean_decode_context(prompt_len: int, gen_tokens: int) -> float:
    """Mean positions attended per decode step: the prompt's first served
    token comes from prefill, then ``gen_tokens - 1`` decode steps attend
    over ``prompt_len + 1 ... prompt_len + gen_tokens - 1`` positions."""
    steps = gen_tokens - 1
    return prompt_len + (steps + 1) / 2.0


def decode_flops(m: Mapping, batch: int, context: float) -> float:
    """One decode step of ``batch`` tokens attending over ``context`` positions."""
    per_token = (
        2.0 * body_params(m)
        + 2.0 * m["d_model"] * m["vocab_size"]
        + 4.0 * m["num_layers"] * m["d_model"] * context
    )
    return batch * per_token


def kv_bytes_per_position(m: Mapping, batch: int) -> float:
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    width = DTYPE_BYTES[m["dtype"]]
    return 2.0 * m["num_layers"] * batch * m["num_kv_heads"] * hd * width


def decode_bytes(m: Mapping, batch: int, context: float) -> float:
    """Least HBM traffic of one decode step: the weights once at the compute
    dtype (the tied table serves the token gather and the LM head) and the
    cached keys and values of ``context`` positions."""
    weights = param_count(m) * DTYPE_BYTES[m["dtype"]]
    return weights + context * kv_bytes_per_position(m, batch)
