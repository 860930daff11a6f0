"""Mesh construction + full sharding layouts for serving and training.

FUNCTIONS, not module-level constants: importing this module never
touches jax device state. Entry points set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before any jax
import so every layout here builds and runs on the CPU container — that
is what the multi-device CI job does.

Production topology (TPU v5e):
  single pod : (16, 16)      axes (data, model)   — 256 chips
  multi-pod  : (2, 16, 16)   axes (pod, data, model) — 512 chips
``model`` is the ICI-contiguous inner axis (TP collectives stay on-chip
-mesh); ``pod`` crosses DCI and carries only gradient reduction (training)
or nothing at all (serving; DESIGN.md §5).

Serving layout (the DeltaDQ deployment, Fig. 2 at scale):

* **base weights** — tensor-parallel along the per-layer-type matmul
  axes (attention qkv/o heads, MLP up/down, MoE experts, SSM inner,
  RG-LRU width; ``repro.dist.DEFAULT_RULES``). The dense base is the
  only multi-GB object, so it is the only thing worth splitting.
* **packed tenant deltas** — replicated by default: post-compression
  they are ~1% of the base, and replication keeps the per-shard delta
  correction collective-free. :func:`delta_shardings` can instead shard
  the output(-group) axis over ``model`` when it divides cleanly.
* **KV cache** — sharded along kv-heads (``repro.dist.cache_axes``),
  batch(slot) rows over ``data`` when it is > 1.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.pack import PackedDelta
from repro.dist import sharding as shd


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with ``Auto`` axis types.

    These layouts place arrays with ``NamedSharding`` and sharding
    constraints and let the partitioner propagate the rest. The default
    ``Explicit`` axes of ``jax.make_mesh`` type every intermediate by its
    sharding instead, and then refuse reshapes such as the head split in
    ``qkv_project`` (``ShardingTypeError``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh (data={data}, model={model}) needs {data * model} "
            f"devices but only {n} are visible")
    return make_mesh((data, model), ("data", "model"))


def make_serving_mesh(devices: Optional[int] = None, *, data: int = 1):
    """(data, model) mesh over ``devices`` local devices (default: all).

    Serving wants the model axis as large as possible (the base is the
    footprint); ``data > 1`` replicates the model shards for decode
    throughput: KV slot rows shard over ``data`` in contiguous pools
    and the engine's scheduler balances per-pool occupancy
    (``ContinuousEngine(mesh=make_serving_mesh(n, data=d))``;
    ``launch.serve --devices n --data d``).
    """
    n = len(jax.devices()) if devices is None else devices
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(
            f"requested {n} devices but only {avail} are visible; on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before the first jax import")
    if n % data:
        raise ValueError(f"data={data} must divide the device count {n} "
                         "(equal contiguous shard pools)")
    return make_mesh((data, n // data), ("data", "model"))


# ---------------------------------------------------------------------------
# Layout assembly (serve profile unless stated otherwise)
# ---------------------------------------------------------------------------
def serve_rules(mesh, **overrides) -> shd.ShardingRules:
    return shd.ShardingRules(mesh).with_overrides(
        **{**shd.SERVE_OVERRIDES, **overrides})


def param_shardings(cfg, mesh, profile: str = "serve", **overrides) -> Any:
    """NamedSharding tree for every base-model parameter class.

    ``serve``: **column-parallel** layout — every >=2-D weight shards its
    output (last) axis over ``model`` when it divides; contraction axes
    are never sharded. With activations pinned replicated at the
    ``apply_linear`` chokepoint (core.apply mesh mode) every matmul then
    reduces over the full contraction locally, in the same order as one
    device — sharded decode is *bit-identical* to single-device decode,
    which is what lets CI assert token identity. The embedding table
    stays replicated (its gather output feeds a norm directly; tied
    unembedding keeps logits replicated for an exact argmax).

    ``train``: the logical-rules layout (``repro.dist``) — Megatron
    row+column TP plus FSDP overrides; there the reduction-order
    difference is irrelevant and memory/collective balance wins.
    """
    from repro.models import lm
    if profile == "train":
        rules = shd.ShardingRules(mesh).with_overrides(
            **{**shd.TRAIN_OVERRIDES, **overrides})
        return shd.tree_shardings(rules, lm.param_specs(cfg),
                                  lm.param_axes(cfg))
    if profile != "serve":
        raise ValueError(
            f"profile {profile!r} not in ('train', 'serve')")
    from repro.core.compress import is_compressible
    n_model = mesh.shape.get("model", 1)
    repl = NamedSharding(mesh, P())

    def one(path: str, leaf) -> NamedSharding:
        # exactly the apply_linear matmul sites (= the delta sites): conv
        # taps, router, norms and the embedding stay replicated because
        # their outputs feed reductions outside the constrained chokepoint
        if not is_compressible(path, leaf):
            return repl
        shape = tuple(leaf.shape)
        if shape[-1] % n_model == 0:
            return NamedSharding(
                mesh, P(*([None] * (len(shape) - 1) + ["model"])))
        return repl

    from repro.utils import map_with_paths
    return map_with_paths(one, lm.param_specs(cfg))


def cache_shardings(cfg, mesh, batch: int, max_seq: int, enc_len: int = 0,
                    **overrides) -> Any:
    """NamedSharding tree for the slot-paged serving cache (KV on heads)."""
    from repro.models import lm
    rules = serve_rules(mesh, **overrides)
    cache = lm.cache_specs(cfg, batch, max_seq, enc_len=enc_len)
    return shd.tree_shardings(rules, cache, shd.cache_axes(cache))


def delta_shardings(deltas: Any, mesh, *, shard_output: bool = False) -> Any:
    """Shardings for a packed-delta tree (possibly tenant-stacked).

    Replicated by default — compressed deltas are tiny, and a replicated
    delta keeps the per-shard correction collective-free. With
    ``shard_output=True``, idx/codes shard their output(-column) axis
    over ``model`` wherever the mesh axis divides it (the layout the
    shard_map'd kernel consumes natively); scale/zero stay replicated.
    """
    n_model = mesh.shape.get("model", 1)
    repl = NamedSharding(mesh, P())

    def one(d: PackedDelta) -> PackedDelta:
        if shard_output and d.h_out % n_model == 0:
            nd = d.idx.ndim
            arr = NamedSharding(mesh, P(*([None] * (nd - 1) + ["model"])))
        else:
            arr = repl
        return PackedDelta(arr, arr, repl, repl, d.h_in, d.h_out, d.h_g,
                           d.keep, d.alpha, d.k_bits, d.m, d.codec)

    return jax.tree.map(one, deltas,
                        is_leaf=lambda x: isinstance(x, PackedDelta))


def replicate(tree: Any, mesh) -> Any:
    """device_put every array leaf fully replicated over the mesh."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def shard_tree(tree: Any, shardings: Any) -> Any:
    """device_put a tree to a matching NamedSharding tree."""
    return jax.tree.map(jax.device_put, tree, shardings)
