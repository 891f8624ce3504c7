"""The yardstick's arithmetic: published peaks, the operations a trained
token needs, and a flash kernel call's operations and bytes. Computed from
shapes alone; checked against hand-worked values in ``checks/``."""

from __future__ import annotations

from typing import Dict

# Published peaks per chip, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
# A device that is not here is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmarks/lib/flops.py")
    return PEAKS[device_kind]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer that a token is multiplied by."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return attn + mlp


def matmul_params(cfg: dict) -> int:
    """Weights every token is multiplied by: the layers and the output
    head. The embedding table is a lookup and is not counted."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    emb = cfg["vocab_size"] * d * (1 if cfg["tie_word_embeddings"] else 2)
    return (emb + cfg["num_hidden_layers"] * (layer_matmul_params(cfg) + 2 * d)
            + d)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul
    weight, plus causal attention. Per layer and sequence the forward does
    two matmuls (QK^T, PV) over the seq*(seq+1)/2 unmasked pairs: 2 * 2 *
    pairs * head_dim * heads; the backward twice that; per token that is
    6 * (seq + 1) * heads * head_dim."""
    attn = (6.0 * (seq + 1) * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])
    return 6.0 * matmul_params(cfg) + attn


# matmuls over the score matrix that each flash kernel needs:
#   fwd: S = QK^T, O = PV;  dq: S, dP = dO V^T, dQ = dS K;
#   dkv: S, dV = P^T dO, dP, dK = dS^T Q
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_ops_bytes(kernel: str, batch: int, seq: int, heads: int,
                    kv_heads: int, head_dim: int,
                    act_bytes: int = 2) -> Dict[str, float]:
    """One causal call of a flash kernel at [batch, seq, heads, head_dim]
    with ``kv_heads`` shared K/V heads: the operations it needs (only
    unmasked pairs) and the least bytes it must move between HBM and the
    chip (each operand read once, each result written once)."""
    pairs = seq * (seq + 1) / 2
    ops = FLASH_MATMULS[kernel] * 2.0 * pairs * head_dim * batch * heads
    q = batch * heads * seq * head_dim * act_bytes        # also o, do, dq
    kv = batch * kv_heads * seq * head_dim * act_bytes    # k or v
    row = batch * heads * seq * 4                         # lse or delta, f32
    if kernel == "fwd":
        moved = q + 2 * kv + q + row                      # q,k,v -> o,lse
    elif kernel == "dq":
        moved = q + 2 * kv + q + 2 * row + q              # q,k,v,do,lse,delta -> dq
    else:
        # dk, dv leave the kernel per QUERY head in float32 (the group sum
        # is outside it): 2 * [batch, heads, seq, head_dim] * 4 bytes
        moved = q + 2 * kv + q + 2 * row + 2 * batch * heads * seq * head_dim * 4
    return {"ops": ops, "bytes": float(moved)}


def roofline_seconds(ops: float, nbytes: float, device_kind: str) -> Dict[str, float]:
    """The least time the chip could take, and which peak sets it."""
    pk = peaks(device_kind)
    t_ops, t_bytes = ops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory"}
