"""Multi-tenant serving engines — the paper's deployment scheme (Fig. 2/3).

One **base model** is resident; each *tenant* (fine-tuned model) registers
only its DeltaDQ-compressed delta. Two engines share that model:

* :class:`ContinuousEngine` — the production path. A continuous-batching
  scheduler packs requests from *mixed tenants* into fixed decode slots
  (``serve.scheduler``), a slot-based paged KV cache admits/evicts
  sequences mid-flight (``serve.kv``), and every decode step serves all
  occupied slots at once: a per-slot tenant-id gather over the
  tenant-stacked packed deltas (``core.apply.SlotDelta``) applies each
  row's correction inside one jitted step. Prompt lengths are bucketed
  and left-padded so jit compiles at most once per bucket.

* :class:`Engine` — the original static per-tenant-batch engine, kept as
  the reference path (``generate``) and as a thin compatibility shim:
  ``serve_batch`` now routes through a ContinuousEngine and falls back to
  the legacy per-tenant grouping only where slot dispatch cannot apply
  (heterogeneous compression specs, MoE expert-site deltas, encdec/vlm
  inputs).

Memory stays the paper's point: base + sum(tiny deltas) instead of N
full fine-tuned models.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.arch import ArchConfig
from repro.core.apply import (
    _is_pd,
    combine_slot_deltas,
    dget,
    get_use_pallas,
    stack_tenant_deltas,
    wrap_slot_deltas,
    zero_delta_like,
)
from repro.core.codecs import runtime_delta_tree
from repro.core.compress import CompressionReport
from repro.core.pack import PackedDelta, decode_values
from repro.models import lm
from repro.serve.kv import SlotKVCache
from repro.serve.metrics import Metrics
from repro.serve.trace import EventBus, attribution, path_label, phase
from repro.serve.scheduler import (
    ChunkBudget,
    ChunkQueue,
    LengthBuckets,
    Request,
    RequestQueue,
    Scheduler,
    SlotState,
    tenant_segments,
    tenant_segments_sharded,
)
from repro.utils import tree_bytes


def mask_after_stop(gen: np.ndarray, stop_token: int) -> np.ndarray:
    """Replace every token *after* the first stop token with the stop token.

    ``gen`` [B, T] int. Explicit zero-filled shift: a stop token in the
    final step must not wrap around and corrupt column 0 (the old
    ``np.roll`` implementation did exactly that).
    """
    stopped = np.cumsum(gen == stop_token, axis=1) > 0
    after = np.zeros_like(stopped)
    after[:, 1:] = stopped[:, :-1]
    return np.where(after, stop_token, gen)


@dataclasses.dataclass
class Tenant:
    name: str
    deltas: Any                       # PackedDelta tree mirroring params
    report: Optional[CompressionReport] = None

    def bytes(self) -> int:
        return tree_bytes(self.deltas)

    def codecs(self) -> tuple:
        """Codec names appearing in this tenant's (runtime) delta tree."""
        names = {l.codec for l in jax.tree.leaves(self.deltas, is_leaf=_is_pd)
                 if _is_pd(l)}
        return tuple(sorted(names))


class DeltaStore:
    """Registry of compressed per-tenant deltas.

    ``version`` bumps on every registration so engines can rebuild their
    tenant-stacked dispatch trees lazily; registration order is stable, so
    tenant row indices never shift under a live scheduler. ``unregister``
    DOES shift rows — ContinuousEngine refuses to continue in-flight
    sequences across it (drain first).
    """

    def __init__(self):
        self._tenants: dict[str, Tenant] = {}
        self.version = 0

    def register(self, name: str, deltas: Any, report=None, *,
                 replace: bool = False) -> Tenant:
        if name in self._tenants and not replace:
            # a silent same-name replace keeps the dict insertion order —
            # so the engine's row-shift guard passes — while live
            # sequences of this tenant switch deltas mid-sequence.
            # Callers that really mean "new version" must say so
            # (ContinuousEngine.register_tenant does, after checking the
            # tenant has no in-flight sequences / via the table rollout).
            raise ValueError(
                f"tenant {name!r} is already registered; pass replace=True "
                "(or use ContinuousEngine.register_tenant, which refuses "
                "only while the tenant has in-flight sequences)")
        t = Tenant(name, deltas, report)
        self._tenants[name] = t
        self.version += 1
        return t

    def unregister(self, name: str) -> None:
        self._tenants.pop(name, None)
        self.version += 1

    def snapshot(self) -> tuple:
        """Cheap copy of the registry state (mapping + version cursor),
        so engine mutations can roll back to exactly this state when a
        refresh fails downstream."""
        return (dict(self._tenants), self.version)

    def restore(self, snap: tuple) -> None:
        self._tenants, self.version = dict(snap[0]), snap[1]

    def get(self, name: str) -> Tenant:
        return self._tenants[name]

    def names(self):
        return sorted(self._tenants)

    def ordered(self) -> List[Tenant]:
        """Tenants in registration order (stable stack rows)."""
        return list(self._tenants.values())

    def total_bytes(self) -> int:
        return sum(t.bytes() for t in self._tenants.values())


# ---------------------------------------------------------------------------
# Pre-decoded delta residency (the hot-tenant value cache)
# ---------------------------------------------------------------------------
def residency_bytes_from_mb(mb: float) -> Optional[int]:
    """``--residency-mb``-style knob -> ``residency_budget_bytes=``.

    Decimal MB; 0 (or negative) disables the tier (None). The ONE
    conversion both the launcher and the benches use, so the unit and
    the disable semantics cannot drift between entry points.
    """
    b = int(mb * 1e6)
    return b if b > 0 else None


class DeltaResidency:
    """LRU cache of *dequantized* per-tenant delta values under a byte budget.

    The packed delta stack stays the ground truth; this tier additionally
    keeps, for up to ``capacity`` hot tenant rows, the f32
    ``pack.decode_values`` output of every leaf (shape = the leaf's idx
    shape — ~8x the packed bytes at k=4, still ~10x under dense). A
    decode step whose unique tenant rows are all resident skips the
    per-step code unpack entirely (the values-given path in
    ``core.apply``/``kernels.fallback``); any other step falls back to
    the packed path, which is always correct.

    * **Budget**: ``capacity = budget_bytes // bytes-per-row`` rows
      (capped at the stack height). Below 2 rows the tier disables
      itself — row 0 (the zero delta) is pinned to residency row 0,
      whose zero-initialized buffer IS its decoded value, so at least
      one real tenant must also fit for the tier to ever apply.
    * **Promotion** is a single jitted buffer-row write per missing
      tenant (donated, so it updates in place); values are decoded by
      the same elementwise ``decode_values`` math the packed path runs
      in-step, so resident values are bit-identical to in-step decode
      and the token-identity contract survives.
    * **Demotion** is LRU among rows not referenced by the current
      step; no device work — the row is simply reused.
    * **Mesh**: value buffers place their output-column axis over
      ``model`` wherever it divides (mirroring
      ``delta_shardings(shard_output=True)``), which is the layout the
      shard_map'd values correction consumes natively.
    """

    def __init__(self, stacked: Any, budget_bytes: int, mesh=None):
        leaves = [l for l in jax.tree.leaves(stacked, is_leaf=_is_pd)
                  if _is_pd(l)]
        if not leaves:
            raise ValueError(
                "residency needs a stacked delta tree with PackedDelta "
                f"leaves; got {type(stacked).__name__} with "
                f"{len(jax.tree.leaves(stacked))} non-delta leaves")
        self.n_rows = int(leaves[0].idx.shape[0])
        self.row_bytes = int(sum(
            4 * int(np.prod(l.idx.shape[1:])) for l in leaves))
        self.budget_bytes = int(budget_bytes)
        self.capacity = int(min(self.n_rows,
                                self.budget_bytes // self.row_bytes))
        self.enabled = self.capacity >= 2
        self.hits = self.misses = self.fallback_steps = 0
        self._stacked = stacked
        self._slot_of: dict[int, int] = {}
        self._lru: List[int] = []        # tenant rows, least-recent first
        self._free: List[int] = []
        self.values: Any = None
        if not self.enabled:
            return
        self.values = jax.tree.map(
            lambda d: jnp.zeros((self.capacity, *d.idx.shape[1:]),
                                jnp.float32),
            stacked, is_leaf=_is_pd)
        if mesh is not None and mesh.shape.get("model", 1) > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            n_model = mesh.shape["model"]
            self.values = jax.tree.map(
                lambda v: jax.device_put(v, NamedSharding(
                    mesh, PartitionSpec(*([None] * (v.ndim - 1)
                                          + ["model"]))
                    if v.shape[-1] % n_model == 0 else PartitionSpec())),
                self.values)
        self._slot_of = {0: 0}           # zero delta: decoded values ARE 0
        self._free = list(range(1, self.capacity))
        self._promote = jax.jit(
            lambda vals, stacked_, row, slot: jax.tree.map(
                lambda d, buf: buf.at[slot].set(decode_values(d.index(row))),
                stacked_, vals, is_leaf=_is_pd),
            donate_argnums=0)

    def ensure(self, rows: np.ndarray) -> Optional[np.ndarray]:
        """Make every unique tenant row of ``rows`` resident, promoting
        (and LRU-demoting) as needed; returns the int32 [n_rows]
        tenant-row -> residency-row map, or None when this step must run
        packed (tier disabled, or more unique tenants than capacity)."""
        if not self.enabled:
            return None
        uniq = [int(r) for r in np.unique(np.asarray(rows)) if r != 0]
        if len(uniq) > self.capacity - 1:     # row 0 keeps its pinned slot
            self.fallback_steps += 1
            return None
        missing = [r for r in uniq if r not in self._slot_of]
        self.hits += len(uniq) - len(missing)
        self.misses += len(missing)
        for r in missing:
            if self._free:
                slot = self._free.pop(0)
            else:
                victim = next(v for v in self._lru if v not in uniq)
                self._lru.remove(victim)
                slot = self._slot_of.pop(victim)
            self._slot_of[r] = slot
            self.values = self._promote(self.values, self._stacked,
                                        jnp.int32(r), jnp.int32(slot))
        for r in uniq:                        # refresh recency, MRU last
            if r in self._lru:
                self._lru.remove(r)
            self._lru.append(r)
        res_map = np.zeros(self.n_rows, np.int32)
        for row, slot in self._slot_of.items():
            res_map[row] = slot
        return res_map

    def invalidate(self, rows) -> None:
        """Drop the pre-decoded values of ``rows`` (their packed source
        was rewritten — a tenant-table rollout/retire reused the row);
        the freed residency slots go back to the promotion free list.
        Row 0 stays pinned: the zero delta's values are always zeros."""
        if not self.enabled:
            return
        for r in rows:
            r = int(r)
            if r == 0:
                continue
            slot = self._slot_of.pop(r, None)
            if slot is not None:
                self._free.append(slot)
            if r in self._lru:
                self._lru.remove(r)

    def retarget(self, stacked: Any) -> None:
        """Point promotions at a rewritten stacked tree. Shapes must be
        unchanged (the tenant table guarantees this), so the promote jit
        does not re-trace."""
        self._stacked = stacked

    def reset_counters(self) -> None:
        """Zero the hit/miss/fallback counters; resident rows stay warm."""
        self.hits = self.misses = self.fallback_steps = 0

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {
            "enabled": self.enabled,
            "capacity_rows": self.capacity,
            "row_bytes": self.row_bytes,
            "budget_bytes": self.budget_bytes,
            # the full capacity*row_bytes buffer is committed at
            # construction; resident_bytes is the HOT subset of it
            "allocated_bytes": (self.capacity if self.enabled else 0)
            * self.row_bytes,
            "resident_rows": len(self._slot_of),
            "resident_bytes": len(self._slot_of) * self.row_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / total if total else None,
            "fallback_steps": self.fallback_steps,
        }


# ---------------------------------------------------------------------------
# Codec groups: tenants whose runtime packings can share one stack
# ---------------------------------------------------------------------------
def _stack_signature(deltas: Any) -> tuple:
    """Per-leaf packing meta of a runtime delta tree. Two tenants can
    join one tenant stack iff their signatures are equal (same meta the
    ``stack_tenant_deltas`` leaf check enforces, including the codec)."""
    return tuple(
        (l.h_in, l.h_out, l.h_g, l.keep, l.k_bits, l.m, l.codec,
         tuple(l.idx.shape), tuple(l.codes.shape))
        for l in jax.tree.leaves(deltas, is_leaf=_is_pd) if _is_pd(l))


@dataclasses.dataclass
class _CodecGroup:
    """One stack-compatible tenant group of a mixed-codec engine.

    ``stacked`` is the group's tenant-stacked runtime tree with the zero
    delta at its row 0; ``lut`` maps a GLOBAL tenant row (the engine's
    ``_rows`` / scheduler numbering) to this group's local stack row —
    rows the group does not own map to 0, the zero delta, so applying
    every group to every batch row and summing is exact (see
    ``core.apply.MultiSlotDelta``).
    """
    stacked: Any
    lut: np.ndarray                   # int32 [n_global_rows]
    names: List[str]
    codecs: tuple


# ---------------------------------------------------------------------------
# Static tenant table: pre-allocated stack rows for hot registration
# ---------------------------------------------------------------------------
class TenantTable:
    """Pre-allocated tenant-stacked envelope with free rows — the slot
    table's pattern applied to tenants.

    The dynamic path re-stacks the whole tenant dimension on every
    register/unregister, so the stacked tree's leading dim (a jit shape)
    changes and the decode step re-traces. The table instead allocates
    ``capacity + 1`` rows up front (row 0 = the zero delta, as in every
    stack) sized from the FIRST tenant's runtime tree, and lifecycle
    events become row writes:

    * **register** fills a free row via one jitted donated per-leaf row
      write (the ``DeltaResidency`` promote / ``SlotKVCache`` insert
      pattern) — array values change, shapes never do, so the decode jit
      signature is constant and hot registration triggers ZERO decode
      recompiles;
    * **retire** tombstones the row (rewrites it with the zero delta, so
      a stale dispatch of that row decodes to an exact 0.0) and returns
      it to the free list — other tenants' rows never shift;
    * **rollout** writes the new version into a *new* row and the engine
      flips the name→row mapping, so in-flight sequences keep decoding
      against the old row until they drain (new requests only).

    Every tenant must match the template's tree structure AND stack
    signature (``check_compatible``) — the same constraint one
    ``_CodecGroup`` enforces; heterogeneous-codec fleets need the
    dynamic multi-group path.

    Under a mesh the table shards exactly like a dynamic stack
    (``delta_shardings(shard_output=True)`` or replicated) and the row
    write pins ``out_shardings`` so hot registration never drifts the
    layout.
    """

    def __init__(self, template: Any, capacity: int, *, mesh=None,
                 shard_deltas: str = "auto"):
        if capacity < 1:
            raise ValueError(f"tenant_capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.signature = _stack_signature(template)
        self.structure = jax.tree.structure(template, is_leaf=_is_pd)
        self.zero = zero_delta_like(template)
        n = self.capacity + 1

        def alloc(d):
            return PackedDelta(
                jnp.zeros((n, *d.idx.shape), d.idx.dtype),
                jnp.zeros((n, *d.codes.shape), d.codes.dtype),
                jnp.zeros((n, *jnp.shape(d.scale)), jnp.float32),
                jnp.zeros((n, *jnp.shape(d.zero)), jnp.int32),
                d.h_in, d.h_out, d.h_g, d.keep, d.alpha, d.k_bits, d.m,
                d.codec)

        self.stacked = jax.tree.map(alloc, template, is_leaf=_is_pd)
        jit_kw = {}
        if mesh is not None:
            from repro.launch import mesh as mesh_lib
            if shard_deltas == "auto":
                sh = mesh_lib.delta_shardings(self.stacked, mesh,
                                              shard_output=True)
            else:
                from jax.sharding import NamedSharding, PartitionSpec
                repl = NamedSharding(mesh, PartitionSpec())
                sh = jax.tree.map(lambda _: repl, self.stacked)
            self.stacked = mesh_lib.shard_tree(self.stacked, sh)
            jit_kw["out_shardings"] = sh

        def _write(stacked, tree, row):
            return jax.tree.map(
                lambda t, d: PackedDelta(
                    t.idx.at[row].set(d.idx),
                    t.codes.at[row].set(d.codes),
                    t.scale.at[row].set(jnp.asarray(d.scale, jnp.float32)),
                    t.zero.at[row].set(jnp.asarray(d.zero, jnp.int32)),
                    t.h_in, t.h_out, t.h_g, t.keep, t.alpha, t.k_bits,
                    t.m, t.codec),
                stacked, tree, is_leaf=_is_pd)

        # donate the table: registration is an in-place row write, not a
        # copy of every registered tenant's bytes
        self._write_jit = jax.jit(_write, donate_argnums=0, **jit_kw)
        self._free: List[int] = list(range(1, n))

    @property
    def n_free(self) -> int:
        return len(self._free)

    def check_compatible(self, tree: Any) -> None:
        """Raise ValueError unless ``tree`` can fill a row (called BEFORE
        any engine state mutates, so a rejected tenant is a no-op)."""
        got_struct = jax.tree.structure(tree, is_leaf=_is_pd)
        if got_struct != self.structure:
            raise ValueError(
                f"tenant delta tree structure {got_struct} does not match "
                f"the tenant table template {self.structure}; cannot "
                "hot-register")
        got_sig = _stack_signature(tree)
        if got_sig != self.signature:
            raise ValueError(
                f"tenant packing meta signature {got_sig!r} does not "
                f"match the tenant table template {self.signature!r}; "
                "heterogeneous-codec fleets need the dynamic "
                "(tenant_capacity=None) engine")

    def alloc(self) -> int:
        """Claim the lowest free row; ValueError when the table is full."""
        if not self._free:
            raise ValueError(
                f"tenant table full ({self.capacity} rows); retire a "
                "tenant or raise tenant_capacity")
        return self._free.pop(0)

    def free(self, row: int) -> None:
        if row in self._free or not 1 <= row <= self.capacity:
            raise ValueError(f"bad tenant-table row free: {row}")
        self._free.append(row)
        self._free.sort()

    def write(self, row: int, tree: Any) -> None:
        """Fill ``row`` from a runtime delta tree (one jitted row write)."""
        self.stacked = self._write_jit(self.stacked, tree, jnp.int32(row))

    def clear(self, row: int) -> None:
        """Tombstone ``row``: rewrite it with the zero delta (same jit
        shape as ``write``, so retirement adds no compile)."""
        self.write(row, self.zero)


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------
class ContinuousEngine:
    """Async continuous-batching server over one base model + N deltas.

    Usage::

        eng = ContinuousEngine(cfg, base_params, n_slots=8, max_seq=256)
        eng.register_tenant("math", deltas)
        req = eng.submit("math", prompt, max_new_tokens=16,
                         on_token=lambda r, tok, done: ...)
        eng.run()                      # drains queue + slots
        req.output()                   # np.ndarray of generated tokens

    jit shape budget: one decode shape (fixed ``n_slots``), one prefill
    shape per length bucket, one cache-insert shape. Mixed tenants share
    all of them.

    ``mesh=`` (a ``(data, model)`` mesh from
    ``launch.mesh.make_serving_mesh``) serves the same loop sharded:
    base weights column-parallel over ``model``, KV rings along
    kv-heads, packed deltas replicated, delta corrections shard_map'd
    per output-column slice — token-identical to the unsharded engine
    (serve/README.md §Mesh serving). Engines with different meshes (or
    none) can coexist in one process; each installs its own mesh before
    stepping.

    ``data=`` (defaulting to the mesh's ``data`` axis extent) splits the
    slot rows into contiguous per-data-shard pools: admission balances
    per-shard occupancy, the decode-step tenant-segment layout is built
    per shard, and KV slot rows live on the shard that admitted them —
    token-identical to ``data=1`` on the same trace (serve/README.md
    §Data-parallel admission).

    ``admission=`` selects the shard-placement policy ("occupancy" —
    the balanced default — or "affinity", which prefers the shard pool
    already hosting the request's tenant within a bounded occupancy
    imbalance, shrinking per-shard unique-tenant counts; or any
    :class:`~repro.serve.scheduler.AdmissionPolicy` instance).

    ``residency_budget_bytes=`` enables the :class:`DeltaResidency`
    tier: hot tenants' dequantized f32 delta values stay resident under
    the byte budget (LRU demotion) and decode steps whose tenants are
    all resident skip the per-step unpack; steps that are not fall back
    to the packed path. Token-identical either way.

    ``chunked_prefill=`` swaps the whole-prompt prefill call for the
    chunk state machine: admission claims the KV slot (reset to the
    clean template) and queues the request on an EDF
    :class:`~repro.serve.scheduler.ChunkQueue`; every step then runs ONE
    combined jit — all decode rows plus at most one ``chunk_size``-token
    prompt chunk threaded through the same tenant-segment delta dispatch
    — so prefilling never preempts in-flight decodes and a burst of
    arrivals amortizes across steps. ``chunk_share`` is the SLO knob
    (:class:`~repro.serve.scheduler.ChunkBudget`): the max fraction of
    steps that may carry chunk work while decodes are active. Token-
    identical to the whole-prompt path (CI-gated at data=1 and the
    (2,4) mesh); serve/README.md §Chunked prefill has the contract.

    ``trace=`` (a :class:`~repro.serve.trace.Tracer`), ``slo=`` (a
    :class:`~repro.serve.telemetry.SLOCounters`) and ``telemetry=`` (a
    :class:`~repro.serve.telemetry.TelemetrySnapshotWriter`) attach
    observability: every hook site emits one typed event on
    ``self.bus`` and all consumers — including ``Metrics`` itself —
    read that same stream. Timestamps come exclusively from the
    injectable clock, so traces are deterministic under
    ``VirtualClock``. Each step also opens profiler phase spans
    (``engine.step`` and its parts, ``serve.trace.phase``) on the
    profiler's clock; their host seconds add up in ``metrics.phases``.
    """

    def __init__(self, cfg: ArchConfig, base_params: Any, *,
                 n_slots: int = 8, max_seq: int = 256, min_bucket: int = 8,
                 store: Optional[DeltaStore] = None, clock=time.monotonic,
                 mesh=None, data: Optional[int] = None,
                 slot_dispatch: str = "segments",
                 shard_deltas: str = "auto",
                 admission="occupancy",
                 residency_budget_bytes: Optional[int] = None,
                 tenant_capacity: Optional[int] = None,
                 chunked_prefill: bool = False, chunk_size: int = 16,
                 chunk_share: float = 1.0,
                 trace=None, slo=None, telemetry=None):
        if cfg.family in ("encdec", "vlm"):
            raise ValueError(
                f"continuous batching does not support family={cfg.family!r} "
                "(per-request encoder inputs); use Engine.generate")
        self.cfg = cfg
        self.mesh = mesh
        # data-parallel slot sharding: slot rows split into `data`
        # contiguous shard pools (mesh `data` axis when a mesh is
        # given; a host-side policy shard otherwise — useful for
        # testing the scheduler without devices). Defaults to the
        # mesh's data extent so `mesh=make_serving_mesh(8, data=2)`
        # is sharded end to end with no second knob.
        mesh_data = mesh.shape.get("data", 1) if mesh is not None else 1
        if data is None:
            data = mesh_data
        if mesh is not None and data != mesh_data:
            raise ValueError(
                f"data={data} does not match the mesh's data axis "
                f"({mesh_data}); slot pools must mirror the device shards")
        if data < 1 or n_slots % data:
            raise ValueError(
                f"n_slots={n_slots} must be a positive multiple of "
                f"data={data} (equal contiguous shard pools)")
        self.data = data
        # "segments": unique-tenant decode dispatch (each distinct delta
        # dequantized once per step); "per_row": the legacy per-row
        # gather path, kept as the behavioral fallback.
        if slot_dispatch not in ("segments", "per_row"):
            raise ValueError(f"slot_dispatch={slot_dispatch!r} not in "
                             "('segments', 'per_row')")
        self.slot_dispatch = slot_dispatch
        # "auto": stacked tenant deltas shard their output-column axis
        # over `model` when it divides (delta_shardings(shard_output=True)),
        # replicated otherwise; "replicated": always replicate.
        if shard_deltas not in ("auto", "replicated"):
            raise ValueError(f"shard_deltas={shard_deltas!r} not in "
                             "('auto', 'replicated')")
        self.shard_deltas = shard_deltas
        cache_sh = None
        if mesh is not None:
            # Sharded serving: base weights tensor-parallel over `model`,
            # KV rings along kv-heads, packed deltas replicated; the delta
            # correction runs shard_map'd per output-column slice
            # (core.apply mesh mode; re-installed per step by
            # _install_mesh so mesh and plain engines can coexist).
            from repro.core.apply import set_mesh
            from repro.launch import mesh as mesh_lib
            self._param_sh = mesh_lib.param_shardings(cfg, mesh)
            base_params = mesh_lib.shard_tree(base_params, self._param_sh)
            cache_sh = mesh_lib.cache_shardings(cfg, mesh, n_slots, max_seq)
            set_mesh(mesh)
        self.base = base_params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.store = store if store is not None else DeltaStore()
        # ssm/rec mixers carry sequence state, so left-padding would
        # pollute it: bucket those archs by exact prompt length instead.
        exact = any(k in ("ssm", "rec") for k in cfg.layer_kinds)
        self.buckets = LengthBuckets(min_bucket=min_bucket,
                                     max_bucket=max_seq, exact=exact)
        self.chunked = bool(chunked_prefill)
        self.chunk_size = int(chunk_size)
        self.chunk_share = float(chunk_share)
        if self.chunked:
            # a chunk may not exceed any layer's ring: C tokens scatter
            # into C distinct slots, and duplicate ring slots within one
            # chunk would collide nondeterministically
            min_ring = min((max_seq if w == 0 else min(w, max_seq))
                           for _, _, w in lm.layer_plan(cfg)
                           ) if cfg.n_layers else max_seq
            if not 1 <= self.chunk_size <= min_ring:
                raise ValueError(
                    f"chunk_size={chunk_size} must be in [1, {min_ring}] "
                    f"(the smallest attention ring of this arch/max_seq)")
        # ssm/rec mixers cannot consume right-padded tail chunks (pad
        # tokens would pollute the carried state): exact archs get
        # exact-length tail chunks (one combined shape per distinct tail
        # length), attn-only archs pad every chunk to chunk_size (ONE
        # combined shape; pad K/V writes are dropped in the model)
        self._chunk_pad = not exact
        self._chunks = ChunkQueue(self.chunk_size)
        self._chunk_budget = ChunkBudget(self.chunk_share)
        self._chunk_t0: dict[int, float] = {}    # rid -> admit time
        self.queue = RequestQueue()
        self.sched = Scheduler(n_slots, self.buckets, data_shards=data,
                               admission=admission)
        self.kv = SlotKVCache(cfg, n_slots, max_seq, shardings=cache_sh,
                              data_shards=data)
        self.metrics = Metrics(n_slots, data_shards=data)
        self.clock = clock
        # Observability: every hook site emits one typed event on the
        # bus; Metrics, the Tracer and SLOCounters are all plain
        # consumers of the same stream (serve.trace). `telemetry` is a
        # TelemetrySnapshotWriter driven by engine time in run().
        self.trace = trace
        self.slo = slo
        self.telemetry = telemetry
        self.bus = EventBus([self.metrics, trace, slo])
        # memoised path-attribution notes per jit call signature: the
        # dispatch layers only report while jax traces, so cached
        # executions replay the notes recorded at trace time
        self._path_notes: dict = {}
        # pre-decoded delta residency: built lazily alongside the tenant
        # stack (it mirrors the stacked tree's shapes) and only under the
        # segments dispatch — the per-row path has no values formulation
        self.residency_budget_bytes = residency_budget_bytes
        self.residency: Optional[DeltaResidency] = None
        # tenant_capacity != None switches lifecycle to TABLE mode: a
        # static pre-allocated tenant-table envelope (built lazily from
        # the first tenant's tree) whose rows are filled/tombstoned in
        # place, so register/rollout/retire never re-stack and never
        # change a decode jit shape. None = the dynamic re-stacking path.
        if tenant_capacity is not None:
            if int(tenant_capacity) < 1:
                raise ValueError(
                    f"tenant_capacity must be >= 1, got {tenant_capacity}")
            if len(self.store.names()) > int(tenant_capacity):
                raise ValueError(
                    f"store already holds {len(self.store.names())} tenants "
                    f"> tenant_capacity={tenant_capacity}")
        self.tenant_capacity = (None if tenant_capacity is None
                                else int(tenant_capacity))
        self._table: Optional[TenantTable] = None
        self._retiring: set = set()      # rolled-out rows awaiting drain

        # host mirrors of per-slot decode state (row 0 = zero delta / base)
        self._tok = np.zeros(n_slots, np.int32)
        self._pos = np.zeros(n_slots, np.int32)
        self._row = np.zeros(n_slots, np.int32)

        self._stacked = None          # tenant-stacked deltas tree (1 group)
        self._groups: List[_CodecGroup] = []   # stack-compatible groups
        self._zero_tree = None        # unstacked all-zero tree (base prefill)
        self._rows: dict[str, int] = {}
        self._store_version = -1
        self._t0: Optional[float] = None

        self._prefill = jax.jit(
            lambda p, b, c, d: lm.prefill(cfg, p, b, c, deltas=d))

        def _step(p, c, t, pos, d):
            logits, c = lm.decode_step(cfg, p, c, t, pos, deltas=d)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), c

        # donate the cache: the decode step updates the (dominant) KV
        # allocation in place instead of copying it every token. In mesh
        # mode, pin the outputs (tokens replicated, cache on its layout)
        # so the donated buffers round-trip without resharding.
        jit_kw = {}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            jit_kw["out_shardings"] = (
                NamedSharding(mesh, PartitionSpec()), cache_sh)
        self._decode = jax.jit(_step, donate_argnums=(1,), **jit_kw)

        # chunked-prefill steps: decode serves ALL slot rows every step
        # (fixed shape), so rows that are free or still mid-prefill get
        # garbage-decoded and then restored from the pre-step cache via
        # the `act` mask — parked rows must keep their (clean or
        # partially prefilled) state bit-exact.
        def _restore(c2, c, act):
            return jax.tree.map(
                lambda new, old: jnp.where(
                    act.reshape(act.shape + (1,) * (new.ndim - 1)), new, old),
                c2, c)

        def _mstep(p, c, t, pos, act, d):
            logits, c2 = lm.decode_step(cfg, p, c, t, pos, deltas=d)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    _restore(c2, c, act))

        def _cstep(p, c, t, pos, act, d, ctok, cpos, cvalid, cslot, cd):
            # slice the chunk row's CLEAN cache before the masked decode
            # garbage-writes it; prefill the chunk against that slice and
            # write the advanced row back after the restore
            row = jax.tree.map(
                lambda l: jax.lax.dynamic_slice_in_dim(l, cslot, 1, axis=0), c)
            logits, c2 = lm.decode_step(cfg, p, c, t, pos, deltas=d)
            c2 = _restore(c2, c, act)
            clog, row2 = lm.prefill_chunk(
                cfg, p, {"tokens": ctok, "positions": cpos, "valid": cvalid},
                row, deltas=cd)
            c2 = jax.tree.map(
                lambda l, r: jax.lax.dynamic_update_slice_in_dim(
                    l, r.astype(l.dtype), cslot, axis=0), c2, row2)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    jnp.argmax(clog, axis=-1).astype(jnp.int32), c2)

        mkw = dict(jit_kw)
        ckw = {}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            repl = NamedSharding(mesh, PartitionSpec())
            ckw["out_shardings"] = (repl, repl, cache_sh)
        self._decode_masked = jax.jit(_mstep, donate_argnums=(1,), **mkw)
        self._combined = jax.jit(_cstep, donate_argnums=(1,), **ckw)
        self.prefill_shapes: set = set()

        # table mode over a pre-populated store: seed the table with the
        # existing tenants (registration order), exactly as if each had
        # been hot-registered — the identity contract between "all
        # tenants up front" and "registered live" starts here
        if self.tenant_capacity is not None and self.store.names():
            for t in self.store.ordered():
                self._table_admit(t.name, t.deltas)
            self._store_version = self.store.version

    # -- tenants ------------------------------------------------------------
    def register_tenant(self, name: str, deltas: Any, report=None) -> Tenant:
        """Register (or roll out a new version of) a tenant.

        ``deltas`` may be any codec's compressed tree (BitDelta leaves,
        low-rank residual leaves, native PackedDelta); it is lowered to
        the PackedDelta runtime layout here, once, so every downstream
        consumer (prefill, decode, residency) sees one format. A tenant
        whose tree structure cannot join the engine must fail here, not
        mid-run inside a prefill (which would leak the claimed slot) —
        and a rejected registration leaves engine state untouched.

        With ``tenant_capacity=`` (table mode) this is HOT: the new
        tenant fills a pre-allocated table row in place, so a running
        engine picks it up with zero decode-step recompiles; re-register
        of an existing name is the rollout path — the new version lands
        in a fresh row and only NEW requests see it, in-flight sequences
        drain against the old row. In dynamic mode a same-name
        re-register is refused while the tenant has in-flight sequences
        (they would silently switch deltas mid-sequence).
        """
        rt = runtime_delta_tree(deltas)
        if self.tenant_capacity is not None:
            rollout = name in self._rows
            old = self._rows.get(name)
            row, _ = self._table_admit(name, rt)     # raises pre-mutation
            t = self.store.register(name, rt, report, replace=rollout)
            self._store_version = self.store.version
            if self.mesh is not None:
                from repro.launch.mesh import replicate
                t.deltas = replicate(t.deltas, self.mesh)
            if rollout:
                self.bus.emit("tenant_rollout", self._now(), tenant=name,
                              row=row, old_row=old,
                              retiring=len(self._retiring))
            else:
                self.bus.emit("tenant_register", self._now(), tenant=name,
                              row=row, free_rows=self._table.n_free)
            return t
        replace = name in self.store.names()
        if replace and self._tenant_in_flight(name):
            raise RuntimeError(
                f"tenant {name!r} has in-flight sequences; re-registering "
                "would switch their deltas mid-sequence — drain first, or "
                "serve with tenant_capacity= for hot version rollout")
        snap = self.store.snapshot()
        t = self.store.register(name, rt, report, replace=replace)
        try:
            self._refresh_stacked()
        except (ValueError, RuntimeError):
            self.store.restore(snap)
            raise
        if self.mesh is not None:
            from repro.launch.mesh import replicate
            t.deltas = replicate(t.deltas, self.mesh)
        self.bus.emit("tenant_rollout" if replace else "tenant_register",
                      self._now(), tenant=name,
                      row=self._rows.get(name), old_row=None)
        return t

    def unregister_tenant(self, name: str) -> None:
        """Retire a tenant.

        Table mode tombstones its row in place (the row is rewritten
        with the zero delta and returned to the free list — no other
        tenant's row shifts, no recompile). Dynamic mode re-stacks the
        remaining tenants. Both refuse while the tenant has in-flight
        sequences or queued requests, and a refused retire leaves engine
        state untouched.
        """
        self.store.get(name)             # KeyError early for unknown names
        if self._tenant_in_flight(name):
            raise RuntimeError(
                f"tenant {name!r} has in-flight sequences; drain before "
                "retiring")
        if any(r.tenant == name for r in self.queue.pending()):
            raise RuntimeError(
                f"tenant {name!r} has queued requests; drain before "
                "retiring")
        if self.tenant_capacity is not None:
            row = self._rows.pop(name)
            self.store.unregister(name)
            self._store_version = self.store.version
            self._table.clear(row)
            self._table.free(row)
            if self.residency is not None:
                self.residency.invalidate([row])
            self._sync_table_group()
            self.bus.emit("tenant_retire", self._now(), tenant=name,
                          row=row, free_rows=self._table.n_free)
            return
        snap = self.store.snapshot()
        self.store.unregister(name)
        try:
            self._refresh_stacked()
        except (ValueError, RuntimeError):
            self.store.restore(snap)
            raise
        self.bus.emit("tenant_retire", self._now(), tenant=name, row=None)

    def _tenant_in_flight(self, name: str) -> bool:
        return any(self.sched.slots[s].request.tenant == name
                   for s in self.sched.active_slots())

    # -- tenant table (hot lifecycle) ---------------------------------------
    def _table_admit(self, name: str, rt: Any) -> tuple:
        """Fill a tenant-table row for ``name`` (no store writes, no
        events — both seeding and hot registration route here). Returns
        ``(row, old_row)``. Everything fallible happens before the first
        mutation, so a rejected tenant leaves the engine untouched."""
        moe = dget(rt, "moe")
        if moe is not None and any(
                isinstance(dget(moe, k), PackedDelta)
                for k in ("wi", "wg", "wo")):
            raise ValueError(
                "slot dispatch cannot apply deltas at MoE expert "
                "sites; serve MoE tenants via per-tenant grouping")
        if self._table is None:
            # first tenant fixes the template: envelope built once, here
            table = TenantTable(rt, self.tenant_capacity, mesh=self.mesh,
                                shard_deltas=self.shard_deltas)
            zero = table.zero
            if self.mesh is not None:
                from repro.launch import mesh as mesh_lib
                zero = mesh_lib.replicate(zero, self.mesh)
            self._table = table
            self._zero_tree = zero
            # ONE group with an identity LUT for the table's whole life:
            # the decode jit signature (len(_groups), shapes) is fixed at
            # capacity, so later registrations can't change it
            lut = np.arange(self.tenant_capacity + 1, dtype=np.int32)
            codecs = tuple(sorted({sig[6] for sig in table.signature}))
            self._groups = [_CodecGroup(stacked=table.stacked, lut=lut,
                                        names=[], codecs=codecs)]
            self._stacked = table.stacked
            if self.residency_budget_bytes \
                    and self.slot_dispatch == "segments":
                self.residency = DeltaResidency(
                    self._stacked, self.residency_budget_bytes,
                    mesh=self.mesh)
        else:
            self._table.check_compatible(rt)
        self._reclaim_retired()
        row = self._table.alloc()        # ValueError when full, pre-mutation
        old = self._rows.get(name)
        self._table.write(row, rt)
        self._rows[name] = row
        if old is not None:
            # rollout: in-flight sequences keep decoding the old row
            # until they drain; tombstone it now if nothing references it
            live = {int(self.sched.slots[s].tenant_row)
                    for s in self.sched.active_slots()}
            if old in live:
                self._retiring.add(old)
            else:
                self._table.clear(old)
                self._table.free(old)
                if self.residency is not None:
                    self.residency.invalidate([old])
        self._sync_table_group()
        return row, old

    def _sync_table_group(self) -> None:
        """Re-point dispatch at the table's current arrays (row writes
        return fresh buffers) — bookkeeping only, shapes never change."""
        g = self._groups[0]
        g.stacked = self._table.stacked
        g.names = [n for n, _ in
                   sorted(self._rows.items(), key=lambda kv: kv[1])]
        self._stacked = self._table.stacked
        if self.residency is not None:
            self.residency.retarget(self._stacked)

    def _reclaim_retired(self) -> None:
        """Tombstone rolled-out rows once their last in-flight sequence
        drains (lazy: checked at request finish and before row alloc)."""
        if not self._retiring:
            return
        live = {int(self.sched.slots[s].tenant_row)
                for s in self.sched.active_slots()}
        done = sorted(self._retiring - live)
        if not done:
            return
        for row in done:
            self._table.clear(row)
            self._table.free(row)
            self._retiring.discard(row)
            if self.residency is not None:
                self.residency.invalidate([row])
        self._sync_table_group()

    def _refresh_stacked(self) -> None:
        if self.tenant_capacity is not None:
            return   # table mode: dispatch state is maintained per row write
        if self._store_version == self.store.version:
            return
        tenants = self.store.ordered()
        # Stage EVERYTHING into locals, validate, then commit: a failed
        # register/unregister must leave the engine exactly as it was
        # (the old code tore down residency and rebuilt _groups/_rows
        # before the in-flight guard could fire, leaving a half-refreshed
        # engine behind the RuntimeError).
        new_groups: List[_CodecGroup] = []
        new_stacked = None
        new_zero = None
        new_rows: dict[str, int] = {}
        if tenants:
            ref_struct = jax.tree.structure(tenants[0].deltas, is_leaf=_is_pd)
            for t in tenants:
                moe = dget(t.deltas, "moe")
                if moe is not None and any(
                        isinstance(dget(moe, k), PackedDelta)
                        for k in ("wi", "wg", "wo")):
                    raise ValueError(
                        "slot dispatch cannot apply deltas at MoE expert "
                        "sites; serve MoE tenants via per-tenant grouping")
                if jax.tree.structure(t.deltas, is_leaf=_is_pd) != ref_struct:
                    # codec groups relax the *packing* meta, not the tree
                    # shape: combining per-group corrections needs every
                    # group's tree to mirror the same param sites
                    raise ValueError(
                        "tenant delta trees differ in structure; "
                        "cannot stack for slot dispatch")
            new_zero = zero_delta_like(tenants[0].deltas)
            new_rows = {t.name: i + 1 for i, t in enumerate(tenants)}
            # partition tenants into stack-compatible groups (first-fit in
            # registration order, so group membership — and therefore each
            # group's local rows — never reorders under appends). Tenants
            # with one codec/spec land in a single group: the existing
            # single-stack behavior, bit for bit.
            buckets: List[tuple] = []    # (signature, [(global_row, Tenant)])
            for i, t in enumerate(tenants):
                sig = _stack_signature(t.deltas)
                for bsig, members in buckets:
                    if bsig == sig:
                        members.append((i + 1, t))
                        break
                else:
                    buckets.append((sig, [(i + 1, t)]))
            n_global = len(tenants) + 1
            for _, members in buckets:
                # row 0 = zero delta so base requests (and rows owned by
                # OTHER groups) share the decode shape and decode to 0
                zero_g = zero_delta_like(members[0][1].deltas)
                stacked_g = stack_tenant_deltas(
                    [zero_g] + [t.deltas for _, t in members])
                lut = np.zeros(n_global, np.int32)
                for local, (grow, _) in enumerate(members, start=1):
                    lut[grow] = local
                if self.mesh is not None:
                    # compressed deltas are tiny: place them across the
                    # mesh once, at registration, not on every decode
                    # step. The stacked dispatch tree shards its
                    # output-column axis over `model` where it divides
                    # (each shard then holds only its slice of the
                    # compressed bytes — the layout the shard_map'd
                    # correction consumes natively); delta_shardings
                    # falls back to replicated per leaf.
                    from repro.launch import mesh as mesh_lib
                    if self.shard_deltas == "auto":
                        stacked_g = mesh_lib.shard_tree(
                            stacked_g,
                            mesh_lib.delta_shardings(stacked_g, self.mesh,
                                                     shard_output=True))
                    else:
                        stacked_g = mesh_lib.replicate(stacked_g, self.mesh)
                codecs = tuple(sorted(
                    {c for _, t in members for c in t.codecs()}))
                new_groups.append(_CodecGroup(
                    stacked=stacked_g, lut=lut,
                    names=[t.name for _, t in members], codecs=codecs))
            # single group == the classic homogeneous engine: keep the
            # stacked tree on its historical attribute (residency and
            # introspection read it); mixed-codec engines expose _groups
            new_stacked = new_groups[0].stacked \
                if len(new_groups) == 1 else None
            if self.mesh is not None:
                from repro.launch import mesh as mesh_lib
                new_zero = mesh_lib.replicate(new_zero, self.mesh)
        # registration is append-only so rows never shift — but a live
        # unregister would remap rows under in-flight sequences, silently
        # decoding them with another tenant's delta. Refuse instead —
        # BEFORE committing (and before allocating residency buffers).
        for slot in self.sched.active_slots():
            state = self.sched.slots[slot]
            want = new_rows.get(state.request.tenant, 0) \
                if state.request.tenant else 0
            if want != state.tenant_row:
                raise RuntimeError(
                    f"tenant stack rows shifted under in-flight request "
                    f"{state.request.rid} (tenant {state.request.tenant!r}); "
                    "drain the engine before unregistering tenants")
        new_res = None
        if tenants and self.residency_budget_bytes \
                and self.slot_dispatch == "segments" \
                and len(new_groups) == 1:
            # the residency tier keys its value buffers to ONE stack's
            # rows; mixed-codec engines serve packed (still correct)
            new_res = DeltaResidency(
                new_stacked, self.residency_budget_bytes, mesh=self.mesh)
        # commit atomically: nothing above mutated engine state
        self.residency = new_res
        self._groups = new_groups
        self._stacked = new_stacked
        self._zero_tree = new_zero
        self._rows = new_rows
        self._store_version = self.store.version

    # -- request API --------------------------------------------------------
    def submit(self, tenant: Optional[str], prompt: np.ndarray, *,
               max_new_tokens: int = 16, stop_token: Optional[int] = None,
               arrival: float = 0.0, deadline: Optional[float] = None,
               on_token=None) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.buckets.bucket(len(prompt))   # raises if no bucket fits
        # live positions are 0..L+new-1; left-pad slots carry invalid
        # positions and may be overwritten, so they don't count against
        # the ring capacity
        if len(prompt) + max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq={self.max_seq}")
        if tenant is not None:
            self.store.get(tenant)   # KeyError early for unknown tenants
        req = self.queue.submit(tenant, prompt, max_new_tokens=max_new_tokens,
                                stop_token=stop_token, arrival=arrival,
                                deadline=deadline, on_token=on_token)
        self.bus.emit("submit", req.arrival, rid=req.rid, tenant=tenant,
                      prompt_len=len(prompt), max_new_tokens=max_new_tokens,
                      deadline=deadline)
        return req

    # -- scheduling core ----------------------------------------------------
    def _now(self) -> float:
        """Engine-relative time; the timebase of Request.arrival/deadline."""
        if self._t0 is None:
            self._t0 = self.clock()
        return self.clock() - self._t0

    def _install_mesh(self) -> None:
        """Install THIS engine's mesh (or None) and slot-dispatch mode as
        the process-global apply-mode before any call that may trace —
        engines with different modes can then coexist in one process
        (each jit traces at most once per shape, under its owner's
        modes)."""
        from repro.core.apply import set_mesh, set_slot_dispatch
        set_mesh(self.mesh)
        set_slot_dispatch(self.slot_dispatch)

    def _phase(self, name: str, **attrs):
        """A profiler span (``serve.trace.phase``) whose host seconds add
        to ``self.metrics.phases``."""
        return phase(name, self.metrics.phases, **attrs)

    def _prefill_into(self, slot: int, req: Request, now: float) -> None:
        L = req.prompt_len
        bucket = self.buckets.bucket(L)
        with self._phase("engine.prefill", rid=req.rid, tenant=req.tenant,
                         prompt_len=L, bucket=bucket):
            with self._phase("engine.prefill.prep"):
                self._install_mesh()
                self._refresh_stacked()
                pad = bucket - L
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, pad:] = req.prompt
                positions = (np.arange(bucket, dtype=np.int32) - pad)[None]
                if req.tenant is not None:
                    deltas = self.store.get(req.tenant).deltas
                else:
                    deltas = self._zero_tree  # None when no tenants registered
                row_cache = lm.init_cache(self.cfg, 1, self.max_seq)
                batch = {"tokens": jnp.asarray(tokens),
                         "positions": jnp.asarray(positions)}
            self.prefill_shapes.add(bucket)
            sig = ("prefill", bucket)
            with self._phase("engine.prefill.dispatch"):
                with attribution() as notes:
                    logits, row_cache = self._prefill(self.base, batch,
                                                      row_cache, deltas)
                if notes:   # dispatch sites only report while jax traces
                    self.bus.emit("jit_trace", now, signature=sig,
                                  site="prefill",
                                  first=sig not in self._path_notes,
                                  notes=list(notes))
                    self._path_notes[sig] = list(notes)
            with self._phase("engine.prefill.insert"):
                self.kv.insert(slot, row_cache)
            with self._phase("engine.prefill.wait"):
                first = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
            with self._phase("engine.prefill.emit"):
                self._prefill_emit(slot, req, now, first, L, bucket)

    def _prefill_emit(self, slot: int, req: Request, now: float, first: int,
                      L: int, bucket: int) -> None:
        t_first = self._now()
        slack = None if req.deadline is None else req.deadline - now
        self.bus.emit("admit", now, rid=req.rid, tenant=req.tenant, slot=slot,
                      wait=now - req.arrival, deadline_slack=slack,
                      prompt_len=L, bucket=bucket)
        self.bus.emit("prefill", t_first, rid=req.rid, tenant=req.tenant,
                      t_start=now, prompt_len=L, bucket=bucket, slot=slot)
        self.bus.emit("first_token", t_first, rid=req.rid, tenant=req.tenant,
                      ttft=t_first - req.arrival)
        self.bus.emit("token", t_first, rid=req.rid, tenant=req.tenant)
        if self.data > 1:
            self.bus.emit("shard_token", t_first,
                          shard=self.sched.shard_of(slot))
        req.t_first_token = t_first
        fin = req.emit(first)

        self._tok[slot] = first
        self._pos[slot] = L
        self._row[slot] = self._rows.get(req.tenant, 0) if req.tenant else 0
        self.sched.place(slot, SlotState(request=req, next_token=first,
                                         pos=L, tenant_row=self._row[slot]))
        if fin:
            self._finish(slot, t_first)

    def _finish(self, slot: int, now: float) -> None:
        state = self.sched.slots[slot]
        req = state.request
        req.t_done = now
        ttft = None if req.t_first_token is None \
            else req.t_first_token - req.arrival
        slack = None if req.deadline is None else req.deadline - now
        self.bus.emit("done", now, rid=req.rid, tenant=req.tenant,
                      latency=now - req.arrival, ttft=ttft,
                      n_tokens=len(req.tokens), deadline_slack=slack)
        self.sched.release(slot)
        self.kv.release(slot)
        # park the freed slot on tenant row 0 so stale rows don't inflate
        # the unique-tenant segment count of subsequent decode steps
        self._row[slot] = 0
        if self._retiring:
            # a rollout's old row may just have lost its last reference
            self._reclaim_retired()

    # -- chunked prefill ----------------------------------------------------
    def _admit_chunked(self, slot: int, req: Request, now: float) -> None:
        """Claim a slot for chunked prefill: no device prefill happens
        here — the request joins the EDF chunk queue and the combined
        step streams its prompt in ``chunk_size``-token chunks."""
        self._install_mesh()
        self._refresh_stacked()
        # the previous occupant's ring pos markers / ssm state would be
        # attended as valid context by mid-sequence appends: reset first
        self.kv.reset(slot)
        row = self._rows.get(req.tenant, 0) if req.tenant else 0
        self._row[slot] = row
        self._tok[slot] = 0
        self._pos[slot] = 0
        self.sched.place(slot, SlotState(request=req, next_token=0, pos=0,
                                         tenant_row=row, prefilling=True))
        self._chunks.add(slot, req)
        self._chunk_t0[req.rid] = now
        slack = None if req.deadline is None else req.deadline - now
        self.bus.emit("admit", now, rid=req.rid, tenant=req.tenant, slot=slot,
                      wait=now - req.arrival, deadline_slack=slack,
                      prompt_len=req.prompt_len, bucket=None)

    def _combined_step(self, now: float) -> bool:
        """One chunked-mode step: all decode rows + at most one prompt
        chunk, inside ONE jit call. Returns False when idle."""
        active = self.sched.active_slots()
        decode_slots = [s for s in active
                        if not self.sched.slots[s].prefilling]
        task = None
        if self._chunk_budget.grant(len(decode_slots), len(self._chunks)):
            task = self._chunks.next_task()
        if task is None and not decode_slots:
            return False
        chunk = {} if task is None else {"chunk_rid": task.request.rid}
        with self._phase("engine.decode", n_active=len(decode_slots),
                         groups=len(self._groups), **chunk):
            with self._phase("engine.decode.prep"):
                self._install_mesh()
                self._refresh_stacked()
                act = np.zeros(self.n_slots, bool)
                act[decode_slots] = True
                # parked slots (free, or mid-prefill) are masked to tenant
                # row 0 so their tenants are not dequantized and don't
                # inflate the unique-tenant segment count
                rows_eff = np.where(act, self._row, 0)
                sd, res_used = self._slot_delta(rows_eff)
                args = (jnp.asarray(self._tok[:, None]),
                        jnp.asarray(self._pos), jnp.asarray(act), sd)
                if task is not None:
                    req = task.request
                    C = self.chunk_size if self._chunk_pad else task.length
                    ctok = np.zeros((1, C), np.int32)
                    ctok[0, :task.length] = req.prompt[task.start:
                                                       task.start + task.length]
                    # pad positions run past every real query position, so
                    # the padded keys are causally masked; their K/V ring
                    # writes are dropped by the model's valid mask
                    cpos = (task.start + np.arange(C, dtype=np.int32))[None]
                    cvalid = np.zeros((1, C), bool)
                    cvalid[0, :task.length] = True
                    cd = self._chunk_delta(int(self._row[task.slot]))
                    args += (jnp.asarray(ctok), jnp.asarray(cpos),
                             jnp.asarray(cvalid), jnp.int32(task.slot), cd)
            if task is None:
                sig = ("decode_masked", len(self._groups), bool(res_used))
                site = "decode_masked"
            else:
                sig = ("combined", C, len(self._groups), bool(res_used))
                site = "combined"
            with self._phase("engine.decode.dispatch"):
                with attribution() as notes:
                    if task is None:
                        nxt, new_cache = self._decode_masked(
                            self.base, self.kv.cache, *args)
                        cn = None
                    else:
                        nxt, cn, new_cache = self._combined(
                            self.base, self.kv.cache, *args)
                if notes:   # non-empty notes == this call (re)traced
                    self.bus.emit("jit_trace", now, signature=sig, site=site,
                                  first=sig not in self._path_notes,
                                  notes=list(notes))
                    self._path_notes[sig] = list(notes)
                self.kv.update(new_cache)
            with self._phase("engine.decode.wait"):
                nxt = np.asarray(nxt)
            with self._phase("engine.decode.emit"):
                path_notes = self._path_notes.get(sig, [])
                t = self._now()
                self.bus.emit(
                    "step", t, t_start=now, n_active=len(decode_slots),
                    chunk_tokens=task.length if task is not None else 0,
                    shard_active=self.sched.shard_occupancy()
                    if self.data > 1 else None,
                    shard_unique=self.sched.shard_unique_tenants(rows_eff),
                    residency_used=res_used,
                    path="base" if sd is None else path_label(path_notes),
                    notes=path_notes, recompiled=bool(notes))
                self._emit_tokens(decode_slots, nxt, t)
                if task is not None:
                    self._advance_chunk(task, now, t, cn, len(decode_slots))
        return True

    def _advance_chunk(self, task, now: float, t: float, cn,
                       n_decode: int) -> None:
        """Book a prompt chunk the step at ``t`` prefilled; after the last
        chunk its first token (from ``cn``) is emitted."""
        req = task.request
        self._chunks.advance(task)
        state = self.sched.slots[task.slot]
        state.pos = task.start + task.length
        self.bus.emit("prefill_chunk", t, rid=req.rid, tenant=req.tenant,
                      slot=task.slot, t_start=now, start=task.start,
                      length=task.length, last=task.last,
                      n_decode=n_decode)
        if task.last:
            # the final chunk's last real position predicts the first
            # generated token — exactly what whole-prompt prefill's
            # h[:, -1:] unembed returns
            first = int(np.asarray(cn)[0, task.length - 1])
            L = req.prompt_len
            self.bus.emit("prefill", t, rid=req.rid, tenant=req.tenant,
                          t_start=self._chunk_t0.pop(req.rid, now),
                          prompt_len=L, bucket=None, slot=task.slot)
            self.bus.emit("first_token", t, rid=req.rid,
                          tenant=req.tenant, ttft=t - req.arrival)
            self.bus.emit("token", t, rid=req.rid, tenant=req.tenant)
            if self.data > 1:
                self.bus.emit("shard_token", t,
                              shard=self.sched.shard_of(task.slot))
            req.t_first_token = t
            self._tok[task.slot] = first
            self._pos[task.slot] = L
            state.prefilling = False
            state.next_token = first
            state.pos = L
            fin = req.emit(first)
            if fin:
                self._finish(task.slot, t)

    def _slot_delta(self, rows: np.ndarray):
        """Per-slot delta dispatch tree for one decode step.

        ``rows`` is the [n_slots] GLOBAL tenant-row vector the step should
        serve (the chunked path masks parked slots to row 0 so their
        tenants are not dequantized). Returns ``(sd, res_used)``.
        """
        sd = None
        res_used = None
        parts = []
        for g in self._groups:
            # group-local rows: slots owned by another group's tenants map
            # to this group's row 0 (the zero delta) and contribute an
            # exact 0.0 to the summed correction — which is what keeps
            # mixed-codec decode token-identical to serving each tenant
            # alone
            rows_g = g.lut[rows]
            seg = None
            values = res_map = None
            if self.slot_dispatch == "segments":
                # host-side layout: rows grouped by tenant, static
                # shapes — the decode jit still compiles exactly once.
                # With data>1 the per-shard [D, B_s] form is built
                # instead: the sort stays within each shard pool and the
                # shard_map'd correction hands every data shard its own
                # pool's rows + segments, so each shard dequantizes only
                # the tenants it actually hosts.
                if self.data > 1:
                    seg = tenant_segments_sharded(rows_g, self.data)
                else:
                    seg = tenant_segments(rows_g)
                seg = jax.tree.map(jnp.asarray, seg)
                # the residency tier targets the XLA host path (it
                # removes the per-step code unpack); under the Pallas
                # backend the segments kernel already decodes each tile
                # once per segment, so attaching values would demote
                # decode to the XLA fallback — checked per step, like
                # the other apply-mode globals in _install_mesh
                if self.residency is not None and not get_use_pallas():
                    # promote this step's tenants into the value cache;
                    # None (over capacity) -> packed path, still correct.
                    # Attaching values changes the SlotDelta pytree
                    # structure, so a residency engine compiles at most
                    # TWO decode shapes (values + packed), not per step.
                    # (Residency only exists when len(_groups) == 1, so
                    # rows_g here is the identity map over `rows`.)
                    rm = self.residency.ensure(rows_g)
                    res_used = rm is not None
                    if res_used:
                        values = self.residency.values
                        res_map = jnp.asarray(rm)
            parts.append(wrap_slot_deltas(g.stacked, jnp.asarray(rows_g),
                                          segments=seg, values=values,
                                          res_map=res_map))
        if parts:
            sd = combine_slot_deltas(parts)
        return sd, res_used

    def _chunk_delta(self, row: int):
        """Batch-1 slot-delta tree for one prefill chunk's tenant row.

        The chunk threads the SAME segment dispatch as decode (one-row
        segment layout), so its per-tenant correction stays token-
        identical to the whole-prompt path's per-tenant prefill.
        """
        if not self._groups:
            return None
        parts = []
        for g in self._groups:
            rows_g = np.asarray([g.lut[row]], np.int32)
            seg = None
            if self.slot_dispatch == "segments":
                seg = jax.tree.map(jnp.asarray, tenant_segments(rows_g))
            parts.append(wrap_slot_deltas(g.stacked, jnp.asarray(rows_g),
                                          segments=seg))
        return combine_slot_deltas(parts)

    def _decode_all(self, now: float) -> None:
        active = self.sched.active_slots()
        if not active:
            return
        with self._phase("engine.decode", n_active=len(active),
                         groups=len(self._groups)):
            with self._phase("engine.decode.prep"):
                self._install_mesh()
                self._refresh_stacked()
                sd, res_used = self._slot_delta(self._row)
                tok = jnp.asarray(self._tok[:, None])
                pos = jnp.asarray(self._pos)
            sig = ("decode", len(self._groups), bool(res_used))
            with self._phase("engine.decode.dispatch"):
                with attribution() as notes:
                    nxt, new_cache = self._decode(self.base, self.kv.cache,
                                                  tok, pos, sd)
                if notes:   # non-empty notes == this call (re)traced
                    self.bus.emit("jit_trace", now, signature=sig,
                                  site="decode",
                                  first=sig not in self._path_notes,
                                  notes=list(notes))
                    self._path_notes[sig] = list(notes)
                self.kv.update(new_cache)
            with self._phase("engine.decode.wait"):
                nxt = np.asarray(nxt)
            with self._phase("engine.decode.emit"):
                path_notes = self._path_notes.get(sig, [])
                t = self._now()
                self.bus.emit(
                    "step", t, t_start=now, n_active=len(active),
                    shard_active=self.sched.shard_occupancy()
                    if self.data > 1 else None,
                    shard_unique=self.sched.shard_unique_tenants(self._row),
                    residency_used=res_used,
                    path="base" if sd is None else path_label(path_notes),
                    notes=path_notes, recompiled=bool(notes))
                self._emit_tokens(active, nxt, t)

    def _emit_tokens(self, slots: List[int], nxt: np.ndarray,
                     t: float) -> None:
        """Hand each decoded slot its token from ``nxt``; finish those
        that are done."""
        for slot in slots:
            state = self.sched.slots[slot]
            req = state.request
            tok = int(nxt[slot])
            self._tok[slot] = tok
            self._pos[slot] += 1
            state.next_token = tok
            state.pos = int(self._pos[slot])
            fin = req.emit(tok)
            self.bus.emit("token", t, rid=req.rid, tenant=req.tenant)
            if self.data > 1:
                self.bus.emit("shard_token", t,
                              shard=self.sched.shard_of(slot))
            if fin:
                self._finish(slot, t)

    def step(self, now: float) -> bool:
        """One scheduler iteration: admit into free slots, then decode."""
        with self._phase("engine.step", n_active=self.sched.n_active,
                         queue=len(self.queue)):
            with self._phase("engine.admit"):
                admitted = self.sched.admit(self.queue, now)
            for slot, req in admitted:
                self.kv.claim(slot)  # kv free list mirrors the slot table
                if self.chunked:
                    self._admit_chunked(slot, req, now)
                else:
                    self._prefill_into(slot, req, now)
            worked = bool(admitted)
            if self.chunked:
                worked = self._combined_step(now) or worked
            elif self.sched.n_active:
                self._decode_all(now)
                worked = True
        return worked

    def run(self, max_steps: int = 1_000_000) -> Metrics:
        """Drain the queue and all slots; returns the metrics collector."""
        self.bus.emit("start", self._now())
        for _ in range(max_steps):
            if not len(self.queue) and not self.sched.n_active:
                break
            now = self._now()
            worked = self.step(now)
            if self.telemetry is not None:
                # driven by the same `now` as the step: zero extra clock
                # reads, deterministic snapshot times under VirtualClock
                self.telemetry.maybe_write(now, self._telemetry_payload)
            if not worked:
                # nothing active and no arrived request: jump (virtual
                # clock) or sleep (real clock) to the next arrival
                nxt = self.queue.next_arrival()
                if nxt is None:
                    break
                if hasattr(self.clock, "advance"):
                    self.clock.advance(max(0.0, nxt - self._now()))
                else:
                    time.sleep(max(0.0, min(0.01, nxt - self._now())))
        else:
            raise RuntimeError(f"serve loop did not drain in {max_steps} steps")
        self.bus.emit("stop", self._now())
        if self.residency is not None:
            self.metrics.residency = self.residency.stats()
        return self.metrics

    def _telemetry_payload(self) -> dict:
        """Snapshot body for the periodic telemetry writer."""
        if self.residency is not None:
            self.metrics.residency = self.residency.stats()
        payload = {"metrics": self.metrics.report()}
        if self.slo is not None:
            payload["slo"] = self.slo.report()
        return payload

    def reset_metrics(self) -> None:
        """Fresh metrics collector (e.g. after jit warmup), same engine.

        Residency *counters* reset with the metrics window; resident
        rows stay warm (they are engine state, like compiled jits). The
        event bus is rebuilt around the new collector; an attached
        tracer/SLO consumer keeps its history (a trace spans the whole
        engine lifetime, like the compiled jits do)."""
        self.metrics = Metrics(self.n_slots, data_shards=self.data)
        self.bus = EventBus([self.metrics, self.trace, self.slo])
        if self.residency is not None:
            self.residency.reset_counters()
        self._t0 = None

    def serve(self, requests: List[tuple], max_new_tokens: int = 16) -> List[np.ndarray]:
        """Convenience: submit (tenant, prompt) pairs, run, return outputs."""
        reqs = [self.submit(t, p, max_new_tokens=max_new_tokens)
                for t, p in requests]
        self.run()
        return [r.output() for r in reqs]


# ---------------------------------------------------------------------------
# Static engine (reference path + compatibility shim)
# ---------------------------------------------------------------------------
class Engine:
    def __init__(self, cfg: ArchConfig, base_params: Any, max_seq: int = 256,
                 clock=time.monotonic):
        self.cfg = cfg
        self.base = base_params
        self.max_seq = max_seq
        self.clock = clock           # forwarded to the serve_batch shim so
        self.store = DeltaStore()    # tests can inject a VirtualClock
        self._prefill = jax.jit(lambda p, b, c, d: lm.prefill(cfg, p, b, c, deltas=d))
        self._decode = jax.jit(lambda p, c, t, pos, d: lm.decode_step(cfg, p, c, t, pos, deltas=d))
        self._cont: Optional[ContinuousEngine] = None

    def register_tenant(self, name: str, deltas: Any, report=None):
        # lower any codec's compressed tree to the PackedDelta runtime
        # layout once here; generate() reads store.get(...).deltas directly
        return self.store.register(name, runtime_delta_tree(deltas), report)

    def generate(self, tenant: Optional[str], prompts: np.ndarray,
                 max_new_tokens: int = 16, stop_token: Optional[int] = None,
                 extra_inputs: Optional[dict] = None) -> np.ndarray:
        """Greedy decode for one tenant group. prompts [B, S] int32.

        tenant=None serves the raw base model (control arm).
        """
        # this engine is unsharded: clear the process-global apply-mode
        # mesh a mesh engine in this process may have left installed
        from repro.core.apply import set_mesh
        set_mesh(None)
        deltas = self.store.get(tenant).deltas if tenant else None
        B, S = prompts.shape
        enc_len = 0
        batch = {"tokens": jnp.asarray(prompts)}
        if extra_inputs:
            batch.update({k: jnp.asarray(v) for k, v in extra_inputs.items()})
            if "enc_feats" in batch:
                enc_len = batch["enc_feats"].shape[1]
        cache = lm.init_cache(self.cfg, B, self.max_seq, enc_len=enc_len)
        logits, cache = self._prefill(self.base, batch, cache, deltas)
        out = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for t in range(max_new_tokens):
            out.append(np.asarray(tok))
            logits, cache = self._decode(self.base, cache, tok[:, None],
                                         jnp.int32(S + t), deltas)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        gen = np.stack(out, axis=1)
        if stop_token is not None:
            gen = mask_after_stop(gen, stop_token)
        return gen

    # -- continuous-batching shim -------------------------------------------
    def _continuous(self) -> ContinuousEngine:
        if self._cont is None:
            self._cont = ContinuousEngine(
                self.cfg, self.base, n_slots=8, max_seq=self.max_seq,
                store=self.store, clock=self.clock)
        return self._cont

    def serve_batch(self, requests: list[tuple[str, np.ndarray]],
                    max_new_tokens: int = 16) -> list[np.ndarray]:
        """Serve a mixed request batch.

        Thin shim over :class:`ContinuousEngine`; falls back to the legacy
        per-tenant static grouping when slot dispatch cannot apply to this
        arch/delta combination. Heterogeneous packing specs and mixed
        codecs are NOT a fallback case anymore: the continuous engine
        partitions tenants into stack-compatible codec groups and sums
        the per-group corrections.
        """
        try:
            eng = self._continuous()
            eng._refresh_stacked()   # raises for non-stackable tenant sets
        except (ValueError, NotImplementedError):
            # slot dispatch inapplicable (MoE deltas, mismatched tree
            # structure, encdec/vlm): legacy per-tenant grouping serves
            return self._serve_batch_grouped(requests, max_new_tokens)
        for tenant, prompt in requests:
            # capacity errors must NOT fall back: the grouped path would
            # silently ring-wrap the cache and truncate context
            L = len(np.asarray(prompt).reshape(-1))
            eng.buckets.bucket(L)
            if L + max_new_tokens > self.max_seq:
                raise ValueError(
                    f"request (prompt {L} + max_new {max_new_tokens}) "
                    f"exceeds max_seq={self.max_seq}")
        return eng.serve(requests, max_new_tokens=max_new_tokens)

    def _serve_batch_grouped(self, requests, max_new_tokens: int = 16):
        """Legacy static path: group requests by tenant, run each group."""
        by_tenant: dict[str, list[int]] = {}
        for i, (tenant, _) in enumerate(requests):
            by_tenant.setdefault(tenant, []).append(i)
        results: list[Optional[np.ndarray]] = [None] * len(requests)
        for tenant, idxs in by_tenant.items():
            lens = {requests[i][1].shape[-1] for i in idxs}
            for L in lens:  # one jit shape per (tenant, prompt-length) group
                group = [i for i in idxs if requests[i][1].shape[-1] == L]
                prompts = np.stack([requests[i][1] for i in group])
                gen = self.generate(tenant, prompts, max_new_tokens)
                for row, i in enumerate(group):
                    results[i] = gen[row]
        return results  # type: ignore

    def memory_report(self) -> dict:
        """Deployment memory ledger.

        Baselines are explicit (the old ``bytes_vs_n_full_models`` divided
        by ``base * (n + 1)``, silently comparing against base + n full
        models):

        * ``bytes_vs_n_full_models``      — ours / (n full fine-tuned
          models), the paper's Fig. 2 comparison: without delta
          compression each tenant ships a full copy.
        * ``bytes_vs_base_plus_n_full``   — ours / (base + n full models),
          for deployments that must also keep the control-arm base.
        """
        base = tree_bytes(self.base)
        deltas = self.store.total_bytes()
        n = len(self.store.names())
        ours = base + deltas
        return {
            "base_bytes": base,
            "delta_bytes_total": deltas,
            "n_tenants": n,
            "bytes_vs_n_full_models": ours / (base * n) if n else 1.0,
            "bytes_vs_base_plus_n_full": ours / (base * (n + 1)),
        }
