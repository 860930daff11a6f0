"""Plain float32 reference of a Mamba-2 language model.

Follows the published description (Dao & Gu, arXiv 2405.21060; the
``mamba_ssm`` Mamba2 block): pre-norm residual blocks whose mixer
projects the input to z, x, B, C and dt, runs a causal depthwise
convolution with bias and SiLU over x, B and C, discretizes with
dt = softplus(dt + dt_bias) and A = -exp(A_log), and runs the selective
state-space recurrence token by token,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t,

then the gated RMSNorm(y * silu(z)) and the output projection; a final
RMSNorm and the output head tied to the embedding. The recurrence is the
sequential scan, not the chunked dual form the program runs. It reads
weights in the served layout (in_proj split into wz, wx, wbc, wdt;
stacked by layer, ``[in, out]``), with norm scales stored as
``weight - 1``. It imports nothing of the program.

``int8=True`` is the control: every matrix product in int8 (weights per
output column, activations per row, symmetric, integer accumulation);
the recurrence, convolution and norms stay float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _quant(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def linear(x, w, int8: bool):
    if not int8:
        return jnp.dot(x, w, precision=_HI)
    xq, sx = _quant(x, -1)
    wq, sw = _quant(w, 0)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def causal_conv(x, w, b):
    """Depthwise causal convolution: x [S, C], w [W, C], b [C]."""
    W = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((W - 1, x.shape[1]), x.dtype), x], 0)
    y = sum(xp[i:i + x.shape[0]] * w[i] for i in range(W))
    return y + b


def mixer(arch: dict, p: dict, i: int, u, int8: bool):
    s = arch["ssm"]
    d_inner = s["expand"] * arch["d_model"]
    P, N, G = s["head_dim"], s["d_state"], s["n_groups"]
    H = d_inner // P
    S = u.shape[0]
    z = linear(u, p["wz"][i], int8)
    x = jax.nn.silu(causal_conv(linear(u, p["wx"][i], int8),
                                p["conv_x_w"][i], p["conv_x_b"][i]))
    bc = jax.nn.silu(causal_conv(linear(u, p["wbc"][i], int8),
                                 p["conv_bc_w"][i], p["conv_bc_b"][i]))
    Bm = jnp.repeat(bc[:, :G * N].reshape(S, G, N), H // G, axis=1)
    Cm = jnp.repeat(bc[:, G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(linear(u, p["wdt"][i], int8) + p["dt_bias"][i])
    A = -jnp.exp(p["a_log"][i])
    xh = x.reshape(S, H, P)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = h * jnp.exp(dtt * A)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, ct, precision=_HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xh, dt, Bm, Cm))
    y = y + xh * p["d_skip"][i][:, None]
    y = rmsnorm(y.reshape(S, d_inner) * jax.nn.silu(z), p["out_norm"][i],
                arch["norm_eps"])
    return linear(y, p["wout"][i], int8)


def logits_at(arch: dict, params: dict, tokens, positions, int8=False):
    """Float32 logits [len(positions), vocab] over ``tokens`` [S], read
    at ``positions``."""
    eps = arch["norm_eps"]
    emb = params["embed"]["tok"]
    if int8:
        eq, es = _quant(emb, 1)
        emb = eq.astype(jnp.float32) * es
    x = emb[tokens]
    p = params["ssm"]
    for i in range(arch["n_layers"]):
        x = x + mixer(arch, p, i, rmsnorm(x, p["norm"][i], eps), int8)
    h = rmsnorm(x[positions], params["final_norm"]["scale"], eps)
    return linear(h, params["embed"]["tok"].T, int8)
