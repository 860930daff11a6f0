"""Unified model zoo: one functional LM covering all assigned families.

Families: dense / moe / ssm / hybrid (decoder-only), encdec (seamless-m4t),
vlm (gated cross-attention). A model is (ArchConfig, params pytree); every
entry point takes an optional ``deltas`` pytree mirroring params (None at
uncompressed leaves) implementing the paper's separate computation.

Param layout: per-kind stacks with a leading layer dim. Uniform archs
(dense/moe/ssm with one layer kind) train via ``lax.scan`` over the stack
(compact HLO, per-layer remat); heterogeneous archs (hybrid/vlm/encdec) and
all cached serving paths walk the layers in a Python loop slicing stacks.

Entry points
    param_specs / param_axes / init_params
    forward(cfg, params, batch, deltas)            -> logits  [train path]
    loss_fn(cfg, params, batch, deltas)            -> (loss, metrics)
    cache_specs / init_cache
    prefill(cfg, params, batch, cache, deltas)     -> (last logits, cache)
    decode_step(cfg, params, cache, tokens, pos, deltas) -> (logits, cache)
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.arch import ArchConfig
from repro.core.apply import apply_linear, dget, dindex, matmul_rows
from repro.models import moe as moe_mod
from repro.models import rglru as rec_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    attention,
    cross_attention,
    glu_mlp,
    qkv_project,
    rmsnorm,
    softcap,
)


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------
def layer_plan(cfg: ArchConfig):
    """[(kind, index_within_kind_stack, window)] for the decoder stack."""
    counters: dict[str, int] = {}
    plan = []
    for i in range(cfg.n_layers):
        kind = cfg.layer_kinds[i]
        j = counters.get(kind, 0)
        counters[kind] = j + 1
        plan.append((kind, j, int(cfg.layer_windows[i])))
    return plan


def kind_counts(cfg: ArchConfig) -> dict[str, int]:
    c: dict[str, int] = {}
    for k in cfg.layer_kinds:
        c[k] = c.get(k, 0) + 1
    return c


def n_cross_blocks(cfg: ArchConfig) -> int:
    if cfg.family == "vlm" and cfg.cross_attn_every:
        return len(range(cfg.cross_attn_every - 1, cfg.n_layers, cfg.cross_attn_every))
    return 0


def n_mlp_layers(cfg: ArchConfig) -> int:
    base = sum(1 for k in cfg.layer_kinds if k in ("attn", "rec"))
    return base + n_cross_blocks(cfg)


# When True, the train path unrolls layers instead of lax.scan. Used by the
# roofline dry-run: SPMD partitioning hides scan trip counts from XLA's
# cost analysis, so unrolled lowering gives truthful per-step FLOP counts
# (EXPERIMENTS.md §Perf, measurement-fix M1).
_FORCE_LOOP = False


def set_force_loop(v: bool) -> None:
    global _FORCE_LOOP
    _FORCE_LOOP = v


def uniform_kind(cfg: ArchConfig) -> Optional[str]:
    """The single layer kind if the arch can use the scan train path."""
    if _FORCE_LOOP:
        return None
    kinds = set(cfg.layer_kinds)
    if len(kinds) == 1 and cfg.family in ("dense", "moe", "ssm"):
        return next(iter(kinds))
    return None


# ---------------------------------------------------------------------------
# Param tables: (name, shape, logical_axes, init)
# ---------------------------------------------------------------------------
def _attn_table(cfg: ArchConfig):
    d, q, kv, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    t = [
        ("ln1", (d,), (None,), "zeros"),
        ("wq", (d, q), ("embed", "heads"), "normal"),
        ("wk", (d, kv), ("embed", "kv_heads"), "normal"),
        ("wv", (d, kv), ("embed", "kv_heads"), "normal"),
        ("wo", (q, d), ("heads", "embed"), "normal"),
    ]
    if cfg.qk_norm:
        t += [("q_norm", (hd,), (None,), "zeros"), ("k_norm", (hd,), (None,), "zeros")]
    return t


def _mlp_table(cfg: ArchConfig):
    d, f = cfg.d_model, cfg.d_ff
    return [
        ("ln", (d,), (None,), "zeros"),
        ("wi", (d, f), ("embed", "mlp"), "normal"),
        ("wg", (d, f), ("embed", "mlp"), "normal"),
        ("wo", (f, d), ("mlp", "embed"), "normal"),
    ]


def _moe_table(cfg: ArchConfig):
    d, m = cfg.d_model, cfg.moe
    t = [
        ("ln", (d,), (None,), "zeros"),
        ("router", (d, m.n_experts), ("embed", None), "normal"),
        ("wi", (m.n_experts, d, m.d_expert), ("experts", "embed", "expert_ff"), "normal"),
        ("wg", (m.n_experts, d, m.d_expert), ("experts", "embed", "expert_ff"), "normal"),
        ("wo", (m.n_experts, m.d_expert, d), ("experts", "expert_ff", "embed"), "normal"),
    ]
    if m.shared_expert:
        t += [
            ("shared/wi", (d, m.d_expert), ("embed", "mlp"), "normal"),
            ("shared/wg", (d, m.d_expert), ("embed", "mlp"), "normal"),
            ("shared/wo", (m.d_expert, d), ("mlp", "embed"), "normal"),
        ]
    return t


def _ssm_table(cfg: ArchConfig):
    d = cfg.d_model
    d_inner, H, P, N = ssm_mod.dims(cfg)[:4]
    G = cfg.ssm.n_groups
    W = cfg.ssm.conv_width
    bc = 2 * G * N
    return [
        ("norm", (d,), (None,), "zeros"),
        ("wz", (d, d_inner), ("embed", "inner"), "normal"),
        ("wx", (d, d_inner), ("embed", "inner"), "normal"),
        ("wbc", (d, bc), ("embed", None), "normal"),
        ("wdt", (d, H), ("embed", None), "normal"),
        ("conv_x_w", (W, d_inner), (None, "inner"), "normal"),
        ("conv_x_b", (d_inner,), (None,), "zeros"),
        ("conv_bc_w", (W, bc), (None, None), "normal"),
        ("conv_bc_b", (bc,), (None,), "zeros"),
        ("a_log", (H,), (None,),
         lambda r, s: jnp.log(jax.random.uniform(r, s, minval=1.0, maxval=16.0))),
        ("d_skip", (H,), (None,), "ones"),
        ("dt_bias", (H,), (None,), lambda r, s: jnp.log(jnp.expm1(
            jax.random.uniform(r, s, minval=1e-3, maxval=0.1)))),
        ("out_norm", (d_inner,), (None,), "zeros"),
        ("wout", (d_inner, d), ("inner", "embed"), "normal"),
    ]


def _rec_table(cfg: ArchConfig):
    d = cfg.d_model
    lru = cfg.rglru.lru_width or d
    W = cfg.rglru.conv_width
    return [
        ("norm", (d,), (None,), "zeros"),
        ("linear_x", (d, lru), ("embed", "lru"), "normal"),
        ("linear_y", (d, lru), ("embed", "lru"), "normal"),
        ("linear_out", (lru, d), ("lru", "embed"), "normal"),
        ("conv_w", (W, lru), (None, "lru"), "normal"),
        ("conv_b", (lru,), (None,), "zeros"),
        ("a_param", (lru,), (None,), lambda r, s: jax.random.uniform(r, s, minval=2.0, maxval=6.0)),
        ("a_gate_w", (lru,), (None,), "normal_vec"),
        ("a_gate_b", (lru,), (None,), "zeros"),
        ("i_gate_w", (lru,), (None,), "normal_vec"),
        ("i_gate_b", (lru,), (None,), "zeros"),
    ]


def _cross_table(cfg: ArchConfig):
    d, q, kv = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return [
        ("ln1", (d,), (None,), "zeros"),
        ("wq", (d, q), ("embed", "heads"), "normal"),
        ("wk", (d, kv), ("embed", "kv_heads"), "normal"),
        ("wv", (d, kv), ("embed", "kv_heads"), "normal"),
        ("wo", (q, d), ("heads", "embed"), "normal"),
        ("gate_attn", (), (), "zeros"),
        ("gate_mlp", (), (), "zeros"),
    ]


def _build_stack(table, n, make):
    out: dict[str, Any] = {}
    for name, shape, axes, init in table:
        fan_in = shape[-2] if len(shape) >= 2 else (shape[0] if shape else 1)
        leaf = make((n, *shape), axes=("layers", *axes), init=init, fan_in=fan_in)
        node = out
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


def _structure(cfg: ArchConfig, make) -> dict:
    counts = kind_counts(cfg)
    tree: dict[str, Any] = {
        "embed": {"tok": make((cfg.vocab, cfg.d_model), axes=("vocab", "embed"),
                              init="embed", fan_in=cfg.d_model)},
        "final_norm": {"scale": make((cfg.d_model,), axes=(None,), init="zeros", fan_in=1)},
    }
    if not cfg.tie_embeddings:
        tree["unembed"] = {"w": make((cfg.d_model, cfg.vocab), axes=("embed", "vocab"),
                                     init="normal", fan_in=cfg.d_model)}
    n_attn = counts.get("attn", 0) + counts.get("moe", 0)
    if n_attn:
        tree["attn"] = _build_stack(_attn_table(cfg), n_attn, make)
    nm = n_mlp_layers(cfg)
    if nm and cfg.d_ff:
        tree["mlp"] = _build_stack(_mlp_table(cfg), nm, make)
    if counts.get("moe"):
        tree["moe"] = _build_stack(_moe_table(cfg), counts["moe"], make)
    if counts.get("ssm"):
        tree["ssm"] = _build_stack(_ssm_table(cfg), counts["ssm"], make)
    if counts.get("rec"):
        tree["rec"] = _build_stack(_rec_table(cfg), counts["rec"], make)
        # pre-FFN norm for rec layers lives in the mlp stack's "ln"
    if cfg.family == "vlm":
        tree["cross"] = _build_stack(_cross_table(cfg), n_cross_blocks(cfg), make)
    if cfg.family == "encdec":
        tree["enc"] = {
            "attn": _build_stack(_attn_table(cfg), cfg.n_enc_layers, make),
            "mlp": _build_stack(_mlp_table(cfg), cfg.n_enc_layers, make),
            "final_norm": {"scale": make((cfg.d_model,), axes=(None,), init="zeros", fan_in=1)},
        }
        tree["dec_cross"] = _build_stack(_cross_table(cfg), cfg.n_layers, make)
    return tree


def param_specs(cfg: ArchConfig):
    def make(shape, *, axes, init, fan_in):
        dtype = jnp.dtype(cfg.param_dtype) if len(shape) >= 3 else jnp.float32
        return jax.ShapeDtypeStruct(shape, dtype)
    return _structure(cfg, make)


def param_axes(cfg: ArchConfig):
    def make(shape, *, axes, init, fan_in):
        return tuple(axes)
    return _structure(cfg, make)


def init_params(cfg: ArchConfig, rng, scale: float = 1.0):
    cnt = [0]

    def make(shape, *, axes, init, fan_in):
        cnt[0] += 1
        r = jax.random.fold_in(rng, cnt[0])
        # stacked leaves: (layers, *shape); >=3 dims = weight matrices -> bf16
        dtype = jnp.dtype(cfg.param_dtype) if len(shape) >= 3 else jnp.float32
        if callable(init):
            return init(r, shape).astype(jnp.float32)
        if init == "zeros":
            return jnp.zeros(shape, dtype if len(shape) >= 3 else jnp.float32)
        if init == "ones":
            return jnp.ones(shape, jnp.float32)
        if init == "normal_vec":
            return (jax.random.normal(r, shape) * 0.1).astype(jnp.float32)
        if init == "embed":
            return (jax.random.normal(r, shape) * scale).astype(dtype)
        std = scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(r, shape) * std).astype(dtype)

    return _structure(cfg, make)


# ---------------------------------------------------------------------------
# Sub-blocks
# ---------------------------------------------------------------------------
def _attn_block_train(cfg, p, d, x, positions, window):
    """Self-attention sub-block, no cache (train/prefill compute)."""
    u = rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = qkv_project(u, p, d, cfg, positions)
    out = attention(q, k, v, positions, positions, window=window, causal=True,
                    cap=cfg.attn_softcap)
    out = apply_linear(out.reshape(*x.shape[:-1], cfg.q_dim), p["wo"], dget(d, "wo"))
    return x + out


def _attn_block_prefill(cfg, p, d, x, positions, window, cache):
    """Train-style attention + cache write of the last S_c tokens.

    ``positions`` is [S] (shared) or [B, S] (per-row, continuous batching:
    left-padded prompts carry negative positions at pad slots, which the
    cache marks invalid so they are never attended).
    """
    u = rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = qkv_project(u, p, d, cfg, positions)
    out = attention(q, k, v, positions, positions, window=window, causal=True,
                    cap=cfg.attn_softcap)
    S = k.shape[1]
    S_c = cache["k"].shape[1]
    n_write = min(S, S_c)
    if positions.ndim == 1:
        pos_w = positions[-n_write:]
        slots = pos_w % S_c
        new_cache = dict(
            k=cache["k"].at[:, slots].set(k[:, -n_write:].astype(cache["k"].dtype)),
            v=cache["v"].at[:, slots].set(v[:, -n_write:].astype(cache["v"].dtype)),
            pos=cache["pos"].at[:, slots].set(pos_w[None]),
        )
    else:
        B = x.shape[0]
        pos_w = positions[:, -n_write:]                   # [B, n_write]
        slots = pos_w % S_c
        bi = jnp.arange(B)[:, None]
        new_cache = dict(
            k=cache["k"].at[bi, slots].set(k[:, -n_write:].astype(cache["k"].dtype)),
            v=cache["v"].at[bi, slots].set(v[:, -n_write:].astype(cache["v"].dtype)),
            pos=cache["pos"].at[bi, slots].set(pos_w),
        )
    out = apply_linear(out.reshape(*x.shape[:-1], cfg.q_dim), p["wo"], dget(d, "wo"))
    return x + out, new_cache


def _attn_block_chunk(cfg, p, d, x, positions, window, cache, valid):
    """Multi-token ring attention for one chunked-prefill row.

    The chunk analogue of ``_attn_block_decode``'s per-row branch:
    ``positions`` [B, C] are absolute prompt positions (a resumable
    cursor offset, NOT starting at 0). Queries attend the pre-write
    ring concatenated with the chunk's own K/V (position-masked, so a
    token sees earlier chunks plus its own prefix), THEN every token's
    K/V is scattered into its ring slot for the chunks/decodes that
    follow. ``valid`` [B, C] bool (or None) marks real tokens in a
    right-padded chunk: pad entries scatter to an out-of-range slot and
    are dropped, so they can never shadow live ring keys (windowed
    layers included), their keys sit at positions past every real
    query (causally masked), and their query outputs are garbage the
    caller discards.
    """
    u = rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = qkv_project(u, p, d, cfg, positions)
    B = x.shape[0]
    S_c = cache["k"].shape[1]
    # Attend BEFORE the ring write, over (old ring ++ this chunk): a
    # windowed layer's ring keeps only the LAST token's window, so
    # writing all C tokens first would evict up to C-1 keys that the
    # chunk's earlier queries still need. The pre-write ring holds every
    # key older than the chunk; the appended segment holds the chunk
    # itself (causally masked by position). Pad keys carry positions
    # past every real query, so the causal mask excludes them.
    k_all = jnp.concatenate([cache["k"], k.astype(cache["k"].dtype)], axis=1)
    v_all = jnp.concatenate([cache["v"], v.astype(cache["v"].dtype)], axis=1)
    kp_all = jnp.concatenate([cache["pos"], positions], axis=1)
    out = attention(q, k_all, v_all, positions, kp_all, window=window,
                    causal=True, cap=cfg.attn_softcap)
    slots = positions % S_c                               # [B, C]
    if valid is not None:
        slots = jnp.where(valid, slots, S_c)              # pad -> dropped
    bi = jnp.arange(B)[:, None]
    ck = cache["k"].at[bi, slots].set(k.astype(cache["k"].dtype), mode="drop")
    cv = cache["v"].at[bi, slots].set(v.astype(cache["v"].dtype), mode="drop")
    cp = cache["pos"].at[bi, slots].set(positions, mode="drop")
    out = apply_linear(out.reshape(*x.shape[:-1], cfg.q_dim), p["wo"], dget(d, "wo"))
    return x + out, dict(k=ck, v=cv, pos=cp)


def _attn_block_decode(cfg, p, d, x, pos, window, cache):
    """Single-token attention over the (ring-buffer) cache.

    ``pos`` scalar: all rows decode at the same position (static batch).
    ``pos`` [B]: per-slot positions (continuous batching) — each row
    writes its own ring slot.
    """
    u = rmsnorm(x, p["ln1"], cfg.norm_eps)
    S_c = cache["k"].shape[1]
    if jnp.ndim(pos) == 1:
        B = x.shape[0]
        positions = pos[:, None]                          # [B, 1]
        q, k, v = qkv_project(u, p, d, cfg, positions)
        slot = pos % S_c                                  # [B]
        bi = jnp.arange(B)
        ck = cache["k"].at[bi, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bi, slot].set(v[:, 0].astype(cache["v"].dtype))
        cp = cache["pos"].at[bi, slot].set(pos)
    else:
        positions = pos[None] if jnp.ndim(pos) == 0 else pos
        q, k, v = qkv_project(u, p, d, cfg, positions)
        slot = pos % S_c
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        cp = cache["pos"].at[:, slot].set(positions[0])
    out = attention(q, ck, cv, positions, cp, window=window, causal=True,
                    cap=cfg.attn_softcap)
    out = apply_linear(out.reshape(*x.shape[:-1], cfg.q_dim), p["wo"], dget(d, "wo"))
    return x + out, dict(k=ck, v=cv, pos=cp)


def _mlp_block(cfg, p, d, x):
    u = rmsnorm(x, p["ln"], cfg.norm_eps)
    return x + glu_mlp(u, p, d, cfg.act)


def _moe_block(cfg, p, d, x):
    u = rmsnorm(x, p["ln"], cfg.norm_eps)
    return x + moe_mod.moe_ffn(u, p, d, cfg)


def _mem_kv(cfg, p, d, memory):
    B, S, _ = memory.shape
    k = apply_linear(memory, p["wk"], dget(d, "wk")).reshape(B, S, cfg.n_kv, cfg.head_dim)
    v = apply_linear(memory, p["wv"], dget(d, "wv")).reshape(B, S, cfg.n_kv, cfg.head_dim)
    return k, v


def _cross_block(cfg, p, d, x, mem_kv, gated: bool):
    u = rmsnorm(x, p["ln1"], cfg.norm_eps)
    B, S, _ = u.shape
    q = apply_linear(u, p["wq"], dget(d, "wq")).reshape(B, S, cfg.n_heads, cfg.head_dim)
    out = cross_attention(q, *mem_kv, cap=cfg.attn_softcap)
    out = apply_linear(out.reshape(B, S, cfg.q_dim), p["wo"], dget(d, "wo"))
    if gated:
        out = out * jnp.tanh(p["gate_attn"].astype(out.dtype))
    return x + out


# ---------------------------------------------------------------------------
# Encoder (encdec family)
# ---------------------------------------------------------------------------
def encode(cfg: ArchConfig, params, feats, deltas=None):
    """Bidirectional encoder over precomputed frontend features [B,S,d]."""
    enc = params["enc"]
    denc = dget(deltas, "enc")
    x = feats.astype(jnp.dtype(cfg.param_dtype))
    S = x.shape[1]
    positions = jnp.arange(S)
    for i in range(cfg.n_enc_layers):
        p_a = _slice(enc["attn"], i)
        d_a = dindex(dget(denc, "attn"), i)
        u = rmsnorm(x, p_a["ln1"], cfg.norm_eps)
        q, k, v = qkv_project(u, p_a, d_a, cfg, positions)
        out = attention(q, k, v, positions, positions, window=0, causal=False,
                        cap=cfg.attn_softcap)
        x = x + apply_linear(out.reshape(*x.shape[:-1], cfg.q_dim), p_a["wo"], dget(d_a, "wo"))
        x = _mlp_block(cfg, _slice(enc["mlp"], i), dindex(dget(denc, "mlp"), i), x)
    return rmsnorm(x, enc["final_norm"]["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Indices into per-kind stacks
# ---------------------------------------------------------------------------
def _slice(tree, i):
    if tree is None:
        return None
    return jax.tree.map(lambda a: a[i], tree)


def _attn_index(cfg, li):
    return sum(1 for k in cfg.layer_kinds[:li] if k in ("attn", "moe"))


def _mlp_index(cfg, li):
    return sum(1 for k in cfg.layer_kinds[:li] if k in ("attn", "rec"))


def _cross_mlp_index(cfg, cross_i):
    n_self = sum(1 for k in cfg.layer_kinds if k in ("attn", "rec"))
    return n_self + cross_i


def _cross_after(cfg) -> set:
    if cfg.family == "vlm" and cfg.cross_attn_every:
        return set(range(cfg.cross_attn_every - 1, cfg.n_layers, cfg.cross_attn_every))
    return set()


# ---------------------------------------------------------------------------
# Layer walk (loop path): used by prefill/decode and heterogeneous training
# ---------------------------------------------------------------------------
def _walk(cfg: ArchConfig, params, x, positions, deltas=None, caches=None,
          memory=None, decode_pos=None, remat=False, chunk=False,
          chunk_valid=None):
    plan = layer_plan(cfg)
    cross_after = _cross_after(cfg)
    has_cache = caches is not None
    new_caches = [None] * (len(caches) if has_cache else 0)
    decode = decode_pos is not None
    ci = cfg.n_layers  # cross caches sit after the self-layer slots

    def mr(fn):
        return jax.checkpoint(fn) if remat else fn

    cross_i = 0
    for li, (kind, j, window) in enumerate(plan):
        cache_l = caches[li] if has_cache else None
        if kind in ("attn", "moe"):
            ai = _attn_index(cfg, li)
            p_a = _slice(params["attn"], ai)
            d_a = dindex(dget(deltas, "attn"), ai)
            if decode:
                x, new_caches[li] = _attn_block_decode(
                    cfg, p_a, d_a, x, decode_pos, window, cache_l)
            elif cache_l is not None and chunk:
                x, new_caches[li] = _attn_block_chunk(
                    cfg, p_a, d_a, x, positions, window, cache_l, chunk_valid)
            elif cache_l is not None:
                x, new_caches[li] = _attn_block_prefill(
                    cfg, p_a, d_a, x, positions, window, cache_l)
            else:
                x = mr(lambda x, p, d: _attn_block_train(
                    cfg, p, d, x, positions, window))(x, p_a, d_a)
            if kind == "moe":
                p_m = _slice(params["moe"], j)
                d_m = dindex(dget(deltas, "moe"), j)
                x = mr(lambda x, p, d: _moe_block(cfg, p, d, x))(x, p_m, d_m)
            else:
                mi = _mlp_index(cfg, li)
                p_m = _slice(params["mlp"], mi)
                d_m = dindex(dget(deltas, "mlp"), mi)
                x = mr(lambda x, p, d: _mlp_block(cfg, p, d, x))(x, p_m, d_m)
        elif kind == "ssm":
            p_s = _slice(params["ssm"], j)
            d_s = dindex(dget(deltas, "ssm"), j)
            fn = lambda x, p, d: ssm_mod.mamba_block(x, p, d, cfg, state=cache_l, decode=decode)
            out, new_st = mr(fn)(x, p_s, d_s) if not has_cache else fn(x, p_s, d_s)
            x = x + out
            if has_cache:
                new_caches[li] = new_st
        elif kind == "rec":
            p_r = _slice(params["rec"], j)
            d_r = dindex(dget(deltas, "rec"), j)
            fn = lambda x, p, d: rec_mod.rglru_block(x, p, d, cfg, state=cache_l, decode=decode)
            out, new_st = mr(fn)(x, p_r, d_r) if not has_cache else fn(x, p_r, d_r)
            x = x + out
            if has_cache:
                new_caches[li] = new_st
            mi = _mlp_index(cfg, li)
            p_m = _slice(params["mlp"], mi)
            d_m = dindex(dget(deltas, "mlp"), mi)
            x = mr(lambda x, p, d: _mlp_block(cfg, p, d, x))(x, p_m, d_m)
        else:
            raise ValueError(f"unknown layer kind {kind}")

        # vlm: gated cross block after every cross_attn_every-th layer
        if li in cross_after:
            p_c = _slice(params["cross"], cross_i)
            d_c = dindex(dget(deltas, "cross"), cross_i)
            if has_cache and decode:
                mem_kv = (caches[ci + cross_i]["k"], caches[ci + cross_i]["v"])
            else:
                mem_kv = _mem_kv(cfg, p_c, d_c, memory)
            if has_cache:
                new_caches[ci + cross_i] = dict(k=mem_kv[0], v=mem_kv[1])
            x = _cross_block(cfg, p_c, d_c, x, mem_kv, gated=True)
            cmi = _cross_mlp_index(cfg, cross_i)
            p_m = _slice(params["mlp"], cmi)
            d_m = dindex(dget(deltas, "mlp"), cmi)
            u = rmsnorm(x, p_m["ln"], cfg.norm_eps)
            x = x + glu_mlp(u, p_m, d_m, cfg.act) * jnp.tanh(p_c["gate_mlp"].astype(x.dtype))
            cross_i += 1

        # encdec: ungated cross-attention into encoder memory, every layer
        if cfg.family == "encdec":
            p_c = _slice(params["dec_cross"], li)
            d_c = dindex(dget(deltas, "dec_cross"), li)
            if has_cache and decode:
                mem_kv = (caches[ci + li]["k"], caches[ci + li]["v"])
            else:
                mem_kv = _mem_kv(cfg, p_c, d_c, memory)
            if has_cache:
                new_caches[ci + li] = dict(k=mem_kv[0], v=mem_kv[1])
            x = _cross_block(cfg, p_c, d_c, x, mem_kv, gated=False)
    return x, new_caches


# ---------------------------------------------------------------------------
# Scan walk (train path for uniform archs)
# ---------------------------------------------------------------------------
def _scan_walk(cfg: ArchConfig, params, x, positions, deltas=None, remat=False):
    kind = uniform_kind(cfg)
    if kind is None:
        raise ValueError(
            f"scan walk needs a uniform layer arch; {cfg.name!r} mixes "
            f"layer_kinds {sorted(set(cfg.layer_kinds))}")
    windows = jnp.asarray(cfg.layer_windows, jnp.int32)

    if kind == "attn":
        xs = {"a": params["attn"], "m": params["mlp"], "w": windows,
              "da": dget(deltas, "attn"), "dm": dget(deltas, "mlp")}

        def body(x, s):
            x = _attn_block_train(cfg, s["a"], s["da"], x, positions, s["w"])
            x = _mlp_block(cfg, s["m"], s["dm"], x)
            return x, None
    elif kind == "moe":
        xs = {"a": params["attn"], "m": params["moe"], "w": windows,
              "da": dget(deltas, "attn"), "dm": dget(deltas, "moe")}

        def body(x, s):
            x = _attn_block_train(cfg, s["a"], s["da"], x, positions, s["w"])
            x = _moe_block(cfg, s["m"], s["dm"], x)
            return x, None
    elif kind == "ssm":
        xs = {"s": params["ssm"], "ds": dget(deltas, "ssm")}

        def body(x, s):
            out, _ = ssm_mod.mamba_block(x, s["s"], s["ds"], cfg, state=None, decode=False)
            return x + out, None
    else:
        raise ValueError(kind)

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, xs)
    return x


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
_EMBED_GATHER_RESHARD = False


def set_embed_gather_reshard(v: bool) -> None:
    """Reshard the embedding table to d@model for the lookup: the gather
    then composes cleanly under SPMD (indices sharded on batch@data, table
    on d@model) instead of triggering involuntary full rematerialization.
    Enabled by mesh-aware launchers; off for single-device tests."""
    global _EMBED_GATHER_RESHARD
    _EMBED_GATHER_RESHARD = v


def embed_tokens(cfg, params, tokens):
    tok = params["embed"]["tok"]
    if _EMBED_GATHER_RESHARD:
        from jax.sharding import PartitionSpec as P
        tok = jax.lax.with_sharding_constraint(tok, P(None, "model"))
    return tok[tokens]


def unembed(cfg, params, h, deltas=None):
    h = rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = matmul_rows(h, params["embed"]["tok"].T)
    else:
        logits = apply_linear(h, params["unembed"]["w"], dget(dget(deltas, "unembed"), "w"))
    return softcap(logits.astype(jnp.float32), cfg.logit_softcap)


def forward(cfg: ArchConfig, params, batch: dict, deltas=None, remat: bool = False):
    """Training/scoring forward: full-sequence causal logits [B,S,V]."""
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.arange(tokens.shape[1])
    if uniform_kind(cfg) is not None:
        h = _scan_walk(cfg, params, x, positions, deltas=deltas, remat=remat)
    else:
        memory = None
        if cfg.family == "encdec":
            memory = encode(cfg, params, batch["enc_feats"], deltas)
        elif cfg.family == "vlm":
            memory = batch["image_embeds"].astype(x.dtype)
        h, _ = _walk(cfg, params, x, positions, deltas=deltas, memory=memory, remat=remat)
    return unembed(cfg, params, h, deltas)


def loss_fn(cfg: ArchConfig, params, batch: dict, deltas=None, remat: bool = False):
    logits = forward(cfg, params, batch, deltas, remat=remat)
    labels = batch.get("labels")
    mask = batch.get("loss_mask")
    if labels is None:
        labels = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)), constant_values=0)
        if mask is None:
            mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - ll) * mask
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"loss": loss, "tokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
def cache_specs(cfg: ArchConfig, batch: int, max_seq: int, enc_len: int = 0):
    """ShapeDtypeStruct tree for the serving cache (dry-run friendly)."""
    dtype = jnp.dtype(cfg.param_dtype)

    def attn_spec(window):
        S_c = max_seq if window == 0 else min(window, max_seq)
        return {
            "k": jax.ShapeDtypeStruct((batch, S_c, cfg.n_kv, cfg.head_dim), dtype),
            "v": jax.ShapeDtypeStruct((batch, S_c, cfg.n_kv, cfg.head_dim), dtype),
            # per-row slot positions: rows advance independently under
            # continuous batching (every cache leaf leads with batch)
            "pos": jax.ShapeDtypeStruct((batch, S_c), jnp.int32),
        }

    out = []
    for kind, j, window in layer_plan(cfg):
        if kind in ("attn", "moe"):
            out.append(attn_spec(window))
        elif kind == "ssm":
            d_inner, H, P, N = ssm_mod.dims(cfg)[:4]
            G = cfg.ssm.n_groups
            W = cfg.ssm.conv_width
            out.append(ssm_mod.SsmState(
                conv_x=jax.ShapeDtypeStruct((batch, W - 1, d_inner), dtype),
                conv_bc=jax.ShapeDtypeStruct((batch, W - 1, 2 * G * N), dtype),
                state=jax.ShapeDtypeStruct((batch, H, P, N), jnp.float32),
            ))
        elif kind == "rec":
            lru = cfg.rglru.lru_width or cfg.d_model
            W = cfg.rglru.conv_width
            out.append(rec_mod.RecState(
                conv=jax.ShapeDtypeStruct((batch, W - 1, lru), dtype),
                h=jax.ShapeDtypeStruct((batch, lru), jnp.float32),
            ))
    if cfg.family == "vlm":
        S_mem = cfg.n_frontend_tokens
        for _ in range(n_cross_blocks(cfg)):
            out.append({
                "k": jax.ShapeDtypeStruct((batch, S_mem, cfg.n_kv, cfg.head_dim), dtype),
                "v": jax.ShapeDtypeStruct((batch, S_mem, cfg.n_kv, cfg.head_dim), dtype),
            })
    if cfg.family == "encdec":
        for _ in range(cfg.n_layers):
            out.append({
                "k": jax.ShapeDtypeStruct((batch, enc_len, cfg.n_kv, cfg.head_dim), dtype),
                "v": jax.ShapeDtypeStruct((batch, enc_len, cfg.n_kv, cfg.head_dim), dtype),
            })
    return out


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, enc_len: int = 0):
    """Zero-initialized serving cache. ``pos`` starts at -1 (invalid)."""
    specs = cache_specs(cfg, batch, max_seq, enc_len)
    out = []
    for spec in specs:
        c = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        if isinstance(c, dict) and "pos" in c:
            c["pos"] = jnp.full(c["pos"].shape, -1, jnp.int32)
        out.append(c)
    return out


def prefill(cfg: ArchConfig, params, batch: dict, cache, deltas=None):
    """Run the prompt through the model, filling caches.

    Returns (logits for the LAST position [B,V], cache).

    ``batch["positions"]`` ([B, S] int32, optional) overrides the default
    ``arange(S)``: the continuous-batching engine left-pads prompts to a
    length bucket and passes negative positions at pad slots, so one jit
    shape serves every prompt length in the bucket.
    """
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    memory = None
    if cfg.family == "encdec":
        memory = encode(cfg, params, batch["enc_feats"], deltas)
    elif cfg.family == "vlm":
        memory = batch["image_embeds"].astype(x.dtype)
    h, new_caches = _walk(cfg, params, x, positions, deltas=deltas, caches=cache,
                          memory=memory)
    logits = unembed(cfg, params, h[:, -1:], deltas)
    return logits[:, 0], new_caches


def prefill_chunk(cfg: ArchConfig, params, batch: dict, cache, deltas=None):
    """Consume one position-offset prompt chunk against an existing cache.

    The resumable middle of chunked prefill: ``batch["tokens"]`` [B, C]
    is a slice of the prompt, ``batch["positions"]`` [B, C] its absolute
    positions (cursor offset — NOT restarting at 0), and ``cache`` the
    row's cache as earlier chunks left it. No left-padding anywhere:
    attention layers ring-append the chunk's K/V and attend the whole
    ring (``_attn_block_chunk``), while ssm/rec mixers continue from
    their carried state exactly like the exact-bucket prefill path (the
    train-mode blocks already thread ``state=`` through). An optional
    ``batch["valid"]`` [B, C] bool marks real tokens when the engine
    right-pads the tail chunk to a fixed width (attention-only archs:
    one jit signature per chunk size; pad K/V writes are dropped, pad
    logits are garbage the caller ignores). Stateful mixers are never
    padded — the engine sends exact-length tail chunks instead.

    Returns (logits [B, C, V] for EVERY chunk position, new cache): the
    caller picks the last real position's logits from the final chunk
    for the first generated token; intermediate chunks' logits are
    compute-and-discard.
    """
    if cfg.family in ("encdec", "vlm"):
        raise ValueError(
            f"chunked prefill does not support family={cfg.family!r} "
            "(per-request encoder inputs); use the whole-prompt path")
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    h, new_caches = _walk(cfg, params, x, batch["positions"], deltas=deltas,
                          caches=cache, chunk=True,
                          chunk_valid=batch.get("valid"))
    return unembed(cfg, params, h, deltas), new_caches


def decode_step(cfg: ArchConfig, params, cache, tokens, pos, deltas=None):
    """One decode step. tokens [B,1] int32; pos scalar int32 (all rows at
    the same position) or [B] int32 (per-slot positions, continuous
    batching — ``deltas`` may then be a slot-dispatched tree).

    Returns (logits [B,V], new cache).
    """
    x = embed_tokens(cfg, params, tokens)
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((1,), pos, jnp.int32)
    h, new_caches = _walk(cfg, params, x, positions, deltas=deltas, caches=cache,
                          memory=None, decode_pos=pos)
    logits = unembed(cfg, params, h, deltas)
    return logits[:, 0], new_caches
