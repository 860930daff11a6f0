"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests that the timed path served, drawn from the seed and always
holding the one with the most tokens, is run through the configuration's
plain float32 reference (``configs/<reference>``), teacher-forced on
prompt + served tokens. At each position where a token was served, the
gap is how far the served token's reference logit lies below the
reference's best, in units of that position's logit RMS. The widest gap
over the sample is compared with the mix's limit.

The reference takes nothing the program made. A tenant's weights are
rebuilt here from the seed (``model.fine_tuned``) and compressed by this
module's own DeltaDQ (paper arXiv 2410.08666, sections 3.3-3.4): per
(group of ``h_g`` inputs, output column), keep the ``h_g / alpha``
entries with the smallest uniform keys drawn from the tenant's
compression key folded with the leaf's path, scale them by alpha, and
quantize them per layer to ``k_bits`` uniform levels between their
minimum and maximum. Separate quantization splits the codes into parts
and changes no value.

``control=True`` puts the control in the program's place: the reference
run in int8 over the same prompts and served tokens, whose first choice
at each of those positions stands for the served token there. Its widest
gap goes through the same limits, so a control run must come out not
correct.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import os
import zlib
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import model as model_lib
from chipbench.traffic import _rng

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAD = 128           # reference inputs are right-padded to a multiple,
SEQ_BLOCK = 512     # and to one of this above it, and the positions
POS_PAD = 64        # read to one of this: few shapes compile


def load_reference(conf: dict):
    path = os.path.join(HERE, "configs", conf["reference"])
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_" + conf["reference"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deltadq_dense(key, delta, *, alpha: float, k_bits, h_g: int):
    """This benchmark's DeltaDQ of one stacked delta [L, h_in, h_out]:
    the dense float32 matrix its packing stands for."""
    L, h_in, h_out = delta.shape
    G, keep = h_in // h_g, int(round(h_g / alpha))
    grouped = delta.reshape(L, G, h_g, h_out)
    u = jax.random.uniform(key, grouped.shape)
    # the `keep` smallest keys of each (group, column); on equal keys the
    # lower position first, as a stable sort would
    _, sel = jax.lax.top_k(-jnp.moveaxis(u, 2, 3), keep)     # [L, G, O, K]
    sel = jnp.moveaxis(sel, 3, 2)                            # [L, G, K, O]
    vals = jnp.take_along_axis(grouped, sel, axis=2) * jnp.float32(alpha)
    if k_bits is not None:
        lo = jnp.min(vals, axis=(1, 2, 3), keepdims=True)
        hi = jnp.max(vals, axis=(1, 2, 3), keepdims=True)
        s = jnp.maximum(hi - lo, 1e-12) / (2 ** k_bits - 1)
        z = jnp.round(-lo / s).astype(jnp.int32)
        q = jnp.clip(jnp.round(vals / s).astype(jnp.int32) + z, 0,
                     2 ** k_bits - 1)
        vals = (q.astype(jnp.float32) - z.astype(jnp.float32)) * s
    # kept positions are distinct: each lands on zeros, exactly
    pos = jnp.arange(h_g, dtype=sel.dtype)[None, None, :, None]
    dense = sum(jnp.where(pos == sel[:, :, k:k + 1], vals[:, :, k:k + 1], 0.0)
                for k in range(keep))
    return dense.reshape(L, h_in, h_out)


@functools.partial(jax.jit, static_argnames=("alpha", "k_bits", "h_g"))
def _merged(b, f, key, *, alpha, k_bits, h_g):
    d = f.astype(jnp.float32) - b.astype(jnp.float32)
    return b.astype(jnp.float32) + deltadq_dense(key, d, alpha=alpha,
                                                 k_bits=k_bits, h_g=h_g)


def owner_params(conf: dict, mix: dict, base: Any, owner, names: List[str],
                 seed: int) -> Dict:
    """Float32 weights of ``owner`` (None = the base model) as a nested
    dict in the served layout."""
    from repro.utils.pytree import map_with_paths
    if owner is None:
        return jax.tree.map(lambda w: w.astype(jnp.float32), base)
    t = names.index(owner)
    e = mix["fleet"]["tenants"][t]
    ft = model_lib.leaf_paths(model_lib.fine_tuned(conf, base, t, seed))
    ck = model_lib.compress_key(seed, t)
    wanted = set(conf["tenant_leaves"])

    def one(path, w):
        if path not in wanted:
            return w.astype(jnp.float32)
        key = jax.random.fold_in(
            ck, zlib.crc32(path.encode("utf-8")) & 0x7FFFFFFF)
        return _merged(w, ft[path], key, alpha=float(e["alpha"]),
                       k_bits=e["k_bits"], h_g=int(e["h_g"]))

    return map_with_paths(one, base)


def sample(records: list, n: int, seed: int) -> list:
    """Up to ``n`` served requests drawn from the seed, the one with the
    most served tokens always among them."""
    served = [r for r in records if r.tokens]
    if len(served) <= n:
        return served
    longest = max(served, key=lambda r: (len(r.prompt) + len(r.tokens),
                                         r.index))
    rest = [r for r in served if r is not longest]
    pick = _rng(seed, 9).choice(len(rest), size=n - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _padded(n: int) -> int:
    step = PAD if n <= SEQ_BLOCK else SEQ_BLOCK
    return -(-n // step) * step


def compare(conf: dict, mix: dict, base: Any, names: List[str], seed: int,
            records: list, *, control: bool = False) -> dict:
    """Widest gap over a sample of ``records`` (objects with .owner,
    .prompt, .tokens, .index): of the served tokens, or with ``control``
    of the tokens the int8 reference puts first at the same positions."""
    ref = load_reference(conf)
    arch = conf["arch"]
    picked = sample(records, int(mix["check_requests"]), seed)
    fn = jax.jit(lambda p, t, pos, i8: ref.logits_at(arch, p, t, pos, i8),
                 static_argnums=(3,))
    gaps, n_tok = [], 0
    owners = sorted({r.owner for r in picked}, key=lambda o: (o is not None,
                                                              o or ""))
    with jax.default_matmul_precision("highest"):
        for owner in owners:
            params = owner_params(conf, mix, base, owner, names, seed)
            for r in [r for r in picked if r.owner == owner]:
                seq = np.concatenate([r.prompt, r.tokens[:-1]]).astype(
                    np.int32)
                S = _padded(len(seq))
                toks = np.zeros(S, np.int32)
                toks[:len(seq)] = seq
                n = len(r.tokens)
                pos = np.full(-(-n // POS_PAD) * POS_PAD, len(seq) - 1,
                              np.int32)
                pos[:n] = np.arange(len(r.prompt) - 1, len(seq))
                lg = np.asarray(fn(params, toks, pos, False),
                                np.float64)[:n]
                if control:
                    served = np.asarray(fn(params, toks, pos, True))[:n] \
                        .argmax(axis=-1)
                else:
                    served = np.asarray(r.tokens, np.int64)
                rms = np.sqrt(np.mean(lg ** 2, axis=-1))
                best = lg.max(axis=-1)
                gaps.append(((best - lg[np.arange(n), served]) / rms).max())
                n_tok += n
            del params
            gc.collect()
    return {"gap_max": float(max(gaps)) if gaps else None,
            "requests": len(picked), "tokens": n_tok,
            "owners": [o if o is not None else "base" for o in owners]}
