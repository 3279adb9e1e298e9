"""Operations of a held-expert configuration, computed from shapes alone.

``counts.py`` counts dense layers; a mixture of experts whose chip holds
some of the experts needs counts of its own.  Everything here is a pure
function of a configuration file's ``model`` block (with a ``moe`` block,
``impl`` ``held``) and a traffic file's sizes; nothing is read from the
program.

* The (token, held expert) pairs a step computes are expected from shapes
  alone: the router's top ``k`` of ``num_experts`` fall on the ``held``
  experts at their share, ``tokens * k * held / num_experts`` a layer.
* The grouped expert matmuls' training FLOPs: per pair, a SwiGLU expert's
  three matmuls of ``d_model x d_ff`` (two without a gate), two FLOPs a
  multiply-add, and the backward twice the forward: ``3 * 2 * 3 * D * F``.
* The whole step, by ``counts.py``'s convention (PaLM, Chowdhery et al.
  2022, appendix B): ``6 N + 12 sum_layers H hd ctx`` a token, with ``N``
  the parameters a token reaches on this chip (each layer's attention,
  router and norms whole, and of its held experts the expected share,
  ``k * held / num_experts`` experts; the embedding and the head) and
  ``ctx`` the positions a layer attends to: its window, or the sequence.
  ``H hd`` is ``d_model`` in ``counts.py``'s models.  Recomputation under
  remat is not counted.
"""

from __future__ import annotations

from typing import List, Mapping


def layer_kinds(m: Mapping) -> List[str]:
    pattern = list(m["pattern"])
    n = m["num_layers"]
    return pattern * (n // len(pattern)) + pattern[: n % len(pattern)]


def _experts(m: Mapping):
    moe = m["moe"]
    held = moe.get("held") or moe["num_experts"]
    return moe["num_experts"], moe["num_experts_per_tok"], held


def _expert_params(m: Mapping) -> int:
    glu = m["activation"] in ("swiglu", "geglu")
    return (3 if glu else 2) * m["d_model"] * m["d_ff"]


def expected_pairs(m: Mapping, tokens: int) -> float:
    """(token, held expert) pairs of ``tokens`` tokens over every layer."""
    e, k, held = _experts(m)
    return tokens * k * held / e * len(layer_kinds(m))


def expert_train_flops(m: Mapping, tokens: int) -> float:
    """Forward and backward FLOPs of the held experts' matmuls."""
    return expected_pairs(m, tokens) * 3 * 2 * _expert_params(m)


def active_params(m: Mapping) -> float:
    """Parameters a token reaches on this chip (module docstring)."""
    d, v = m["d_model"], m["vocab_size"]
    hd = m.get("head_dim") or d // m["num_heads"]
    e, k, held = _experts(m)
    attn = d * m["num_heads"] * hd * 2 + 2 * d * m["num_kv_heads"] * hd
    layer = attn + d * e + 2 * d + k * held / e * _expert_params(m)
    return v * d * (1 if m.get("tie_embeddings") else 2) + d + len(layer_kinds(m)) * layer


def attended(m: Mapping, kind: str, seq_len: int) -> int:
    window = m["local_window"] if kind == "local_attn" else m.get("window")
    return seq_len if window is None else min(window, seq_len)


def train_flops_per_token(m: Mapping, seq_len: int) -> float:
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    attention = sum(12.0 * m["num_heads"] * hd * attended(m, kind, seq_len) for kind in layer_kinds(m))
    return 6.0 * active_params(m) + attention
