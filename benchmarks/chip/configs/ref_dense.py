"""Plain float32 reference of a dense decoder: Phi-3 (and any model of
the same block: RMSNorm, rotary GQA attention, SwiGLU MLP).

Follows the published description (Phi-3 technical report, arXiv
2404.14219; the Hugging Face ``Phi3ForCausalLM`` block): pre-norm
residual blocks, rotary embedding in the rotate-half form, grouped-query
causal softmax attention, SwiGLU feed-forward, final RMSNorm and an
untied output head. It reads weights in the served layout (separate
q/k/v and gate/up matrices, stacked by layer, ``[in, out]``), with norm
scales stored as ``weight - 1``. It imports nothing of the program.

``int8=True`` is the control: every matrix product in int8, weights
quantized per output column and activations per row, symmetric, with
integer accumulation; softmax and norms stay float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512      # queries per attention block: bounds the score tensor


def _quant(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def linear(x, w, int8: bool):
    if not int8:
        return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    xq, sx = _quant(x, -1)
    wq, sw = _quant(w, 0)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, pos, theta):
    """x [S, H, D] rotated by position, rotate-half form."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal GQA. q [S, Hq, D]; k, v [S, Hkv, D] -> [S, Hq * D]."""
    S, Hq, D = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(S, Hkv, Hq // Hkv, D)
    kpos = jnp.arange(S)

    def block(args):
        qb, start = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(D)
        qpos = start + jnp.arange(qb.shape[0])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    nb = max(1, S // Q_BLOCK)
    bq = S // nb
    out = jax.lax.map(block, (qg.reshape(nb, bq, Hkv, Hq // Hkv, D),
                              jnp.arange(nb) * bq))
    return out.reshape(S, Hq * D)


def logits_at(arch: dict, params: dict, tokens, positions, int8=False):
    """Float32 logits [len(positions), vocab] of the causal model over
    ``tokens`` [S] (S a multiple of Q_BLOCK, or at most one block),
    read at ``positions``."""
    eps = arch["norm_eps"]
    Hq, Hkv, D = arch["n_heads"], arch["n_kv"], arch["head_dim"]
    S = tokens.shape[0]
    emb = params["embed"]["tok"]
    if int8:
        eq, es = _quant(emb, 1)
        emb = eq.astype(jnp.float32) * es
    x = emb[tokens]
    pos = jnp.arange(S)
    at, ml = params["attn"], params["mlp"]
    for i in range(arch["n_layers"]):
        u = rmsnorm(x, at["ln1"][i], eps)
        q = rope(linear(u, at["wq"][i], int8).reshape(S, Hq, D), pos,
                 arch["rope_theta"])
        k = rope(linear(u, at["wk"][i], int8).reshape(S, Hkv, D), pos,
                 arch["rope_theta"])
        v = linear(u, at["wv"][i], int8).reshape(S, Hkv, D)
        x = x + linear(attention(q, k, v), at["wo"][i], int8)
        u = rmsnorm(x, ml["ln"][i], eps)
        h = jax.nn.silu(linear(u, ml["wg"][i], int8)) \
            * linear(u, ml["wi"][i], int8)
        x = x + linear(h, ml["wo"][i], int8)
    h = rmsnorm(x[positions], params["final_norm"]["scale"], eps)
    head = params["embed"]["tok"].T if arch["tie_embeddings"] \
        else params["unembed"]["w"]
    return linear(h, head, int8)
