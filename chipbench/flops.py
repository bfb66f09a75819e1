"""Operations and bytes the algorithm needs, from shapes alone.

``model_flops_per_token``: the forward and backward of a dense decoder,
counted as 6 x (matmul weights a token passes through) + 6 x L x S x H x D
for causal attention (each query meets S/2 keys on average; two products in
the forward, four in the backward).  Recomputed operations are not counted,
and neither are norms, activations or the optimizer.

The flash kernels' counts are per call of the kernel on a ``(B, H, S, D)``
problem with ``KV`` key/value heads, over the ``S (S + 1) / 2`` causal
query-key pairs.  The forward does two products per pair (QK^T, PV).  The
backward, its dq and dk/dv kernels together, needs five: the scores again,
dP = dO V^T, dV = P^T dO, dQ = dS K and dK = dS^T Q.  Bytes are each
operand read once and each result written once.
"""

from __future__ import annotations

from typing import Dict


def matmul_params(m: Dict) -> int:
    """Weights every token is multiplied by: the layers and the projection
    onto the vocabulary (the embedding lookup is a gather, not a product)."""
    d, f, dh = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    hq, hkv = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * f
    return m["num_hidden_layers"] * per_layer + m["vocab_size"] * d


def model_flops_per_token(m: Dict, seq_len: int) -> float:
    attn = 6 * m["num_hidden_layers"] * seq_len * m["num_attention_heads"] * m["head_dim"]
    return 6.0 * matmul_params(m) + attn


def causal_pairs(S: int) -> int:
    return S * (S + 1) // 2


def flash_fwd(B: int, H: int, KV: int, S: int, D: int) -> Dict[str, float]:
    flops = 4.0 * B * H * D * causal_pairs(S)
    elt = 2  # bfloat16
    bytes_ = elt * B * S * D * (H + 2 * KV) + elt * B * H * S * D + 4 * B * H * S
    return {"flops": flops, "bytes": float(bytes_)}


def flash_bwd(B: int, H: int, KV: int, S: int, D: int) -> Dict[str, float]:
    """The dq and dk/dv kernels of one backward, together.  They take k and
    v expanded to all H heads, and the row statistics lse and delta."""
    flops = 10.0 * B * H * D * causal_pairs(S)
    elt = 2
    reads = elt * B * H * S * D * 4 + 4 * B * H * S * 2  # q, k, v, dO; lse, delta
    writes = elt * B * H * S * D * 3  # dq, dk, dv
    return {"flops": flops, "bytes": float(reads + writes)}


def least_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s."""
    return max(work["flops"] / peak["bf16_flops_per_s"], work["bytes"] / peak["hbm_bytes_per_s"])
