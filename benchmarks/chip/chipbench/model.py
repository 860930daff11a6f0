"""The model a cell serves: its program configuration, its weights and
its tenants, all made from the seed.

A configuration file (``configs/<name>.json``) states the published
shape keys, what was cut (``reduced``) and assumed, and an ``arch``
block that is the program's ``ArchConfig`` as data. Weights are drawn on
the device in one jitted call, in the layout and types the program
serves (``repro.models.lm.param_specs``), from the rules in the file's
``init`` map. Each tenant's fine-tuned weights differ from the base in
the file's ``tenant_leaves`` only, by ``delta_rms`` times the leaf's
initial scale; they are compressed through the program's own
``repro.core.compress`` at the fleet's DeltaDQ specs.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.traffic import seed_words

# fold-in tags that keep the seed's streams apart
_BASE, _DELTA, _COMPRESS = 0x0BA5E, 0x0DE17A, 0x0C0DEC


def seed_key(seed: int) -> jax.Array:
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)


def path_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.arch import ArchConfig, SsmCfg
    a = dict(conf["arch"])
    if a.get("ssm") is not None:
        a["ssm"] = SsmCfg(**a["ssm"])
    return ArchConfig(**a)


def leaf_paths(tree: Any) -> Dict[str, Any]:
    from repro.utils.pytree import flatten_with_paths
    return flatten_with_paths(tree)


def _rule(conf: dict, path: str) -> list:
    init = conf["init"]
    return init.get(path, init["*"])


def init_std(conf: dict, path: str, shape: tuple) -> float:
    """The standard deviation the ``fan_in`` or ``normal`` rule gives a
    leaf (the scale tenant deltas are drawn at)."""
    rule = _rule(conf, path)
    if rule[0] == "fan_in":
        return 1.0 / math.sqrt(shape[-2])
    if rule[0] == "normal":
        return float(rule[1])
    raise ValueError(f"{path}: rule {rule} has no scale for a delta")


def _draw(rule: list, key, shape, fan_in_dim: int) -> jnp.ndarray:
    kind = rule[0]
    if kind == "fan_in":
        return jax.random.normal(key, shape) / math.sqrt(shape[fan_in_dim])
    if kind == "normal":
        return jax.random.normal(key, shape) * float(rule[1])
    if kind == "uniform":
        return jax.random.uniform(key, shape, minval=rule[1], maxval=rule[2])
    if kind == "log_uniform":
        return jnp.log(jax.random.uniform(key, shape, minval=rule[1],
                                          maxval=rule[2]))
    if kind == "softplus_inverse_uniform":
        u = jax.random.uniform(key, shape, minval=rule[1], maxval=rule[2])
        return jnp.log(jnp.expm1(u))
    raise ValueError(f"unknown init rule {rule}")


def make_params(conf: dict, cfg, seed: int) -> Any:
    """Base weights in the program's layout and types, on the device,
    from one jitted call."""
    from repro.models import lm
    from repro.utils.pytree import map_with_paths
    specs = lm.param_specs(cfg)
    key = jax.random.fold_in(seed_key(seed), _BASE)

    def build(key):
        def one(path, spec):
            v = _draw(_rule(conf, path), path_key(key, path), spec.shape, -2)
            return v.astype(spec.dtype)
        return map_with_paths(one, specs)

    return jax.jit(build)(key)


@functools.partial(jax.jit, static_argnames=("scales",))
def _moved(leaves: dict, key, scales: tuple) -> dict:
    out = {}
    for path, scale in scales:
        w = leaves[path]
        d = jax.random.normal(path_key(key, path), w.shape) * scale
        out[path] = (w.astype(jnp.float32) + d).astype(w.dtype)
    return out


def fine_tuned(conf: dict, base: Any, tenant: int, seed: int) -> Any:
    """Tenant ``tenant``'s fine-tuned weights: the base tree with each
    ``tenant_leaves`` leaf moved by ``delta_rms`` x its initial scale
    (rounded to the served type). Other leaves are the base's arrays."""
    from repro.utils.pytree import map_with_paths
    key = jax.random.fold_in(jax.random.fold_in(seed_key(seed), _DELTA),
                             tenant)
    flat = leaf_paths(base)
    wanted = sorted(conf["tenant_leaves"])
    missing = set(wanted) - set(flat)
    if missing:
        raise ValueError(f"tenant_leaves not in the model: {sorted(missing)}")
    rms = float(conf["delta_rms"])
    scales = tuple((p, rms * init_std(conf, p, flat[p].shape))
                   for p in wanted)
    new = _moved({p: flat[p] for p in wanted}, key, scales)
    return map_with_paths(lambda p, w: new.get(p, w), base)


def compress_key(seed: int, tenant: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(seed_key(seed), _COMPRESS),
                              tenant)


def spec_of(entry: dict):
    from repro.core.compress import DeltaDQSpec
    return DeltaDQSpec(alpha=float(entry["alpha"]), k_bits=entry["k_bits"],
                       m=int(entry["m"]), h_g=int(entry["h_g"]))


def make_tenants(conf: dict, mix: dict, base: Any, names: List[str],
                 seed: int) -> List[Tuple[str, Any, Any]]:
    """[(name, packed deltas, report)], compressed through the program's
    ``compress`` at each fleet entry's DeltaDQ spec, one tenant at a
    time so only one fine-tuned copy is alive."""
    from repro.core.compress import compress
    out = []
    for t, (name, entry) in enumerate(zip(names, mix["fleet"]["tenants"])):
        ft = fine_tuned(conf, base, t, seed)
        deltas, report = compress(base, ft, spec_of(entry),
                                  rng=compress_key(seed, t))
        del ft
        out.append((name, deltas, report))
    return out
