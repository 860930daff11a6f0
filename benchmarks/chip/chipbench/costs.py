"""Operations, bytes and peaks, from shapes.

FLOPs are those of the equivalent merged fine-tuned model, whatever
implements the correction: 2 per weight of every linear map (the
unembedding included), attention's two products over the live context,
and a state-space layer's recurrence. So no formulation of the
correction can raise the count, and no share of a peak can pass 100%.

Bytes a decode step must move: the base weights read once, at the
configuration's 2 bytes each (of the embedding table, the rows looked
up, unless the table is also the output head), the packed bytes of each
distinct tenant in the step, and each row's cache: keys and values of
its live context, or its state-space and convolution state read and
written.
"""
from __future__ import annotations

import math

# Published peaks, one chip (Google Cloud documentation, "TPU v5e").
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud, TPU v5e: 197 TFLOP/s bf16, "
                              "819 GB/s, 16 GB HBM"},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _ssm_dims(a: dict):
    s = a["ssm"]
    d_inner = s["expand"] * a["d_model"]
    return d_inner, d_inner // s["head_dim"], s["head_dim"], s["d_state"], \
        s["n_groups"], s["conv_width"]


def layer_linear_params(a: dict) -> int:
    """Weights of one layer's linear maps."""
    d = a["d_model"]
    if a["family"] == "ssm":
        d_inner, H, _, N, G, _ = _ssm_dims(a)
        return d * (2 * d_inner + 2 * G * N + H) + d_inner * d
    q, kv = a["n_heads"] * a["head_dim"], a["n_kv"] * a["head_dim"]
    return d * (q + 2 * kv) + q * d + 3 * d * a["d_ff"]


def flops_per_token(a: dict, ctx: int) -> float:
    """Model FLOPs of one token whose context (itself included) holds
    ``ctx`` positions."""
    L, d, V = a["n_layers"], a["d_model"], a["vocab"]
    f = 2.0 * L * layer_linear_params(a) + 2.0 * d * V
    if a["family"] == "ssm":
        d_inner, H, P, N, G, W = _ssm_dims(a)
        # decay, input outer product and update (4 per state entry), the
        # C contraction (2), the depthwise convolution (2 per tap)
        f += L * (6.0 * H * P * N + 2.0 * W * (d_inner + 2 * G * N))
    else:
        f += L * 4.0 * a["n_heads"] * a["head_dim"] * ctx
    return f


def prompt_flops(a: dict, prompt_len: int) -> float:
    """Model FLOPs of a prompt: every position over its own prefix."""
    L = prompt_len
    base = flops_per_token(a, 0) * L
    if a["family"] == "ssm":
        return base
    return base + a["n_layers"] * 4.0 * a["n_heads"] * a["head_dim"] \
        * L * (L + 1) / 2


def base_weight_bytes(a: dict, rows: int) -> float:
    """Base weights a step must read once, every weight at 2 bytes (the
    configuration's bfloat16): a program that keeps some in float32
    reads more than this, never less."""
    L, d, V = a["n_layers"], a["d_model"], a["vocab"]
    w = L * layer_linear_params(a)
    head = d * V
    emb = head if a["tie_embeddings"] else head + rows * d
    if a["family"] == "ssm":
        d_inner, H, _, N, G, W = _ssm_dims(a)
        # vectors: norms, conv taps and biases, A, D, dt bias
        w += L * (d + d_inner + (W + 1) * (d_inner + 2 * G * N) + 3 * H)
    else:
        w += L * 2 * d
    return 2.0 * (w + emb + d)


def packed_leaf_bytes(h_in: int, h_out: int, layers: int, t: dict) -> float:
    """Bytes of one stacked leaf's DeltaDQ packing: one index byte per
    kept entry, codes packed at 1/2/4/8 bits (float32 without k_bits),
    a scale and a zero per layer."""
    keep = int(round(t["h_g"] / t["alpha"]))
    G = h_in // t["h_g"]
    k = t["k_bits"]
    if k is None:
        per_col = keep * 4
    else:
        width = next(w for w in (1, 2, 4, 8) if k <= w)
        per_col = math.ceil(keep / (8 // width))
    return layers * (G * h_out * (keep + per_col) + 8.0)


def tenant_bytes(leaf_shapes: dict, t: dict) -> float:
    """Packed bytes of one tenant: ``leaf_shapes`` maps each delta leaf
    to its stacked shape (layers, h_in, h_out)."""
    return sum(packed_leaf_bytes(s[1], s[2], s[0], t)
               for s in leaf_shapes.values())


def cache_bytes(a: dict, ctx: int) -> float:
    """One row's cache traffic in a decode step at context ``ctx``."""
    L = a["n_layers"]
    if a["family"] == "ssm":
        d_inner, H, P, N, G, W = _ssm_dims(a)
        return L * 2.0 * (4.0 * H * P * N + 2.0 * (W - 1)
                          * (d_inner + 2 * G * N))
    return L * 2.0 * 2.0 * ctx * a["n_kv"] * a["head_dim"]
