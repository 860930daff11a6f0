"""Set-up and the measured window of a closed-loop serving cell.

Set-up builds the base weights and the tenant fleet from the seed,
registers the tenants with one ``ContinuousEngine`` (the serving path,
default options), and warms every program the cell's traffic can reach:
one prefill per (codec group or base model, prompt shape), where a
prompt shape is a length bucket or, for engines that bucket by exact
length, each length the mix can deal, plus the decode step. Where the
mix fills the slots in set-up, each client's first request is admitted
there too. A fill reaches only its own seed's shapes, so it warms too
little for the next seed's fill or for a window in which requests turn
over.

The window drives only ``submit`` and ``step``. Each client sends its
next request as soon as the previous one finished. Tokens are stamped on
this module's clock when the ``step`` that produced them returns. The
window ends at the first step boundary after ``seconds``; rates divide
by the time actually measured.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import model as model_lib
from chipbench.traffic import Traffic, tenant_names


@dataclass
class Record:
    """One request as its client saw it."""
    index: int
    owner: Optional[str]
    prompt: np.ndarray
    max_new_tokens: int
    t_submit: float
    tokens: List[int] = field(default_factory=list)
    stamps: List[float] = field(default_factory=list)
    t_done: Optional[float] = None


@dataclass
class Step:
    t_start: float
    t_end: float
    decode_rows: List[tuple]          # (owner, context) per decode token
    prefills: List[tuple]             # (owner, prompt_len) admitted


@dataclass
class Window:
    t0: float
    t_end: float
    records: List[Record]
    steps: List[Step]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


class Cell:
    """One cell's engine and clients; ``setup`` then ``run_window``."""

    def __init__(self, conf: dict, mix: dict, seed: int, *, log=print,
                 span: Callable = None):
        self.conf, self.mix, self.seed = conf, mix, int(seed)
        self.log = log
        # host spans around submit and step (profiler annotations when
        # the run is traced)
        self.span = span or (lambda name: nullcontext())
        self.names = tenant_names(mix)
        self.records: Dict[int, Record] = {}
        self._pending: List[tuple] = []
        self._free_clients: List[int] = []
        self._client_of: Dict[int, int] = {}
        self._steps: List[Step] = []

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.serve import ContinuousEngine
        conf, mix = self.conf, self.mix
        self.cfg = model_lib.arch_config(conf)
        t = time.perf_counter()
        self.base = model_lib.make_params(conf, self.cfg, self.seed)
        jax.block_until_ready(self.base)
        self.log(f"setup: base weights {time.perf_counter() - t:.3f}s")
        t = time.perf_counter()
        tenants = model_lib.make_tenants(conf, mix, self.base, self.names,
                                         self.seed)
        jax.block_until_ready([d for _, d, _ in tenants])
        self.delta_shapes = {
            p: tuple(int(x) for x in leaf.shape)
            for p, leaf in model_lib.leaf_paths(self.base).items()
            if p in set(conf["tenant_leaves"])}
        self.log(f"setup: {len(tenants)} tenants compressed "
                 f"{time.perf_counter() - t:.3f}s")
        self.traffic = Traffic(mix, self.names, self.cfg.vocab, self.seed)
        self.engine = ContinuousEngine(self.cfg, self.base,
                                       n_slots=int(mix["slots"]),
                                       max_seq=self.traffic.max_seq())
        for name, deltas, report in tenants:
            self.engine.register_tenant(name, deltas, report)
        del tenants
        t = time.perf_counter()
        n = self._warm()
        self.log(f"setup: warmed {n} prefill shapes and the decode step "
                 f"{time.perf_counter() - t:.3f}s")
        self.engine.reset_metrics()
        self._free_clients = list(range(int(mix["clients"])))
        if mix["fill_in_setup"]:
            self._send_all()
            while len(self.engine.queue):
                self._step()
            self._steps.clear()

    def _warm(self) -> int:
        """Run one request per (owner kind, prompt shape) the mix can
        reach, and one that decodes where the mix decodes; nothing is
        stamped."""
        eng = self.engine
        groups = {}
        for name, entry in zip(self.names, self.mix["fleet"]["tenants"]):
            key = (entry["alpha"], entry["k_bits"], entry["m"], entry["h_g"])
            groups.setdefault(key, name)
        owners = [None] + list(groups.values())
        # one prompt per shape: the longest length the mix deals in it
        shapes: Dict[int, int] = {}
        for L in self.traffic.prompt_levels:
            b = eng.buckets.bucket(L)
            shapes[b] = max(shapes.get(b, 0), L)
        lengths = [shapes[b] for b in sorted(shapes)]
        rng = np.random.default_rng(0)
        for owner in owners:
            for L in lengths:
                eng.submit(owner, rng.integers(0, self.cfg.vocab, L),
                           max_new_tokens=1, arrival=eng._now())
                eng.run()
        if max(self.traffic.output_levels) > 1:
            eng.submit(owners[-1], rng.integers(0, self.cfg.vocab,
                                                lengths[0]),
                       max_new_tokens=2, arrival=eng._now())
            eng.run()
        return len(owners) * len(shapes)

    # -- clients --------------------------------------------------------
    def _send(self, client: int) -> None:
        spec = self.traffic.next()
        t = time.perf_counter()
        rec = Record(spec.index, spec.owner, spec.prompt,
                     spec.max_new_tokens, t)
        with self.span("bench.submit"):
            req = self._submit(spec)
        self.records[req.rid] = rec
        self._client_of[req.rid] = client

    def _submit(self, spec):
        eng = self.engine
        return eng.submit(spec.owner, spec.prompt,
                          max_new_tokens=spec.max_new_tokens,
                          arrival=eng._now(), on_token=self._on_token)

    def _send_all(self) -> None:
        while self._free_clients:
            self._send(self._free_clients.pop(0))

    def _on_token(self, req, tok, done) -> None:
        self._pending.append((req.rid, tok, done))

    def _step(self) -> float:
        """One engine step; stamps its tokens and lets finished clients
        send again. Returns the stamp."""
        eng = self.engine
        t0 = time.perf_counter()
        with self.span("bench.step"):
            eng.step(eng._now())
        t = time.perf_counter()
        rows, pre = [], []
        for rid, tok, done in self._pending:
            rec = self.records[rid]
            k = len(rec.tokens)
            rec.tokens.append(tok)
            rec.stamps.append(t)
            ctx_len = len(rec.prompt) + k
            if k == 0:
                pre.append((rec.owner, len(rec.prompt)))
            else:
                rows.append((rec.owner, ctx_len))
            if done:
                rec.t_done = t
                self._free_clients.append(self._client_of.pop(rid))
        self._pending.clear()
        self._steps.append(Step(t0, t, rows, pre))
        self._send_all()
        return t

    # -- window ---------------------------------------------------------
    def run_window(self, seconds: float) -> Window:
        t0 = time.perf_counter()
        self._steps.clear()
        self._send_all()
        while True:
            t = self._step()
            if t - t0 >= seconds:
                break
        return Window(t0, t, list(self.records.values()), list(self._steps))
