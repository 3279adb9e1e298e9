"""The held experts' grouped matmuls' share of the chip's bf16 peak, in %.

Their forward and backward FLOPs on a chip's tokens, counted from shapes
alone (``moe_counts.expert_train_flops``: the expected (token, held expert)
pairs times ``3 * 2 * 3 * D * F``, recomputation not counted), over the
device time under ``moe_experts`` per step (``moe_experts_ms.train``).
Bound by compute: at these widths the matmuls' bytes take a fraction of
their FLOPs' time.
"""

from benchmarks.chip import moe_counts, spans


def read(rec):
    ms = spans.scope_ms(rec, ["moe_experts"])
    if ms is None:
        return None
    t = rec.traffic
    tokens = t["global_batch"] * t["seq_len"] // rec.chips
    return 100.0 * moe_counts.expert_train_flops(rec.model, tokens) / (ms / 1e3) / rec.peak["bf16_flops_per_s"]
