"""Bring-up smoke: serve Llama-3.2-1B at its published widths on a TPU.

    python chip_smoke.py              # one chip: kernels, serving, checks
    python chip_smoke.py --chips 4    # four chips: the mesh path only
    python chip_smoke.py --layers 16 --no-warm   # all 16 layers, one pass

Everything runs in this one process: a chip belongs to one process at a
time. Weights are random, made from ``--seed``, at the model's published
widths, with depth cut to LAYERS unless ``--layers`` says otherwise.
One chip runs, in order:

1. kernels: each Pallas kernel compiled (not interpreted) at one
   Llama-3.2-1B width with a lane-aligned group size, against the XLA
   fallback on the same chip;
2. serving: the full-width base plus four DeltaDQ tenants at two
   compression ratios through ``ContinuousEngine``, the serving default
   path, with one base-model request among the tenants' requests;
3. correctness: (a) every request's tokens equal per-tenant
   ``Engine.generate``'s, or part from them only at a near-tie of the
   reference's logits (TIE_MARGIN); (b) prefill and decode logits,
   through the programs ``Engine.generate`` runs, agree with a float32
   ``lm.forward`` on ``base + reconstruct_dense(delta)``; (c) a tenant's
   logits differ from the base's.

``--chips 4`` serves the same stream on a (data=1, model=4) and a
(data=2, model=2) mesh, and holds each to a single-device engine in this
process as (a) does.

Every phase prints what it measured as it ends. The last line of stdout
is one JSON object, ``{"ok": true, "device": {...}}``; it is printed only
when every phase passed. Without a TPU the script exits non-zero first.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

ARCH = "llama3.2-1b"
N_SLOTS = 8
MAX_SEQ = 1024
# The served correction is the XLA gather formulation: on a v5e, at all
# 16 layers, a warm 8-slot decode step or a prefill took ~23 s, and a
# run at all 16 layers without the warm pass took ~14 min and peaked at
# 15.2 of the chip's 16 GB. By default depth is cut to LAYERS at full
# width, and prompts and outputs are short, to keep the run well inside
# a 20-minute limit; ``--layers 16`` runs the whole model. Every layer
# runs the same code, so a cut layer exercises nothing new.
LAYERS = 2
MAX_NEW = 4
# one prompt length, equal to a length bucket: no left padding separates
# the engine from Engine.generate, and each program compiles once
PROMPT_LEN = 8
RATIOS = (16, 128, 16, 128)   # one tenant per entry, two ratios

# Kernel vs XLA fallback, as max |kernel - ref| / max |ref|. The kernels
# decode exactly (elementwise dequant, one nonzero per one-hot sum) and
# then multiply f32 operands in the MXU, which may round them to bf16
# (8 significant bits, 2^-9 relative each): a product then carries up to
# 2^-8 and a sum over h_g terms of random sign stays far below 1% of the
# largest output. A wrong index, code or scale moves outputs by O(1).
KERNEL_TOL = 1e-2
# dequant has no matmul: it must agree with reconstruct_dense to f32
# rounding of the one multiply it shares.
DEQUANT_TOL = 1e-6
# Engine logits vs the float32 reference, as RMS(engine - ref) / RMS(ref).
# The served path keeps K/V in bf16 and runs matmuls at the backend's
# default precision: one bf16 MXU pass on a TPU, about 2^-8 of each
# product's size. A matmul's error is then ~0.4% of its output's RMS, and
# up to 16 layers of 7 matmuls feeding one residual stream add such
# errors like a random walk: sqrt(112) * 0.4% ~ 4% of the logits' RMS.
# 10% bounds it at any depth. A missing or misplaced delta moves the logits by O(1), and
# (c) requires each tenant to move them by TENANT_MIN_SHIFT.
LOGIT_TOL = 0.1
TENANT_MIN_SHIFT = 4 * LOGIT_TOL
# Token identity with Engine.generate is exact on the CPU, where both
# engines run the same arithmetic. On a TPU, XLA does not promise equal
# bits for different batch shapes: a reduction tiled another way rounds
# another way, and one flipped bf16 rounding then spreads. So a greedy
# token may flip where the reference's two best logits nearly tie: at
# the first divergence the engine's token must lie within TIE_MARGIN *
# RMS of the reference's best logit. Measured on a v5e: at 2 layers 9 of
# 9 requests are identical; at all 16 layers 8 of 9 are, and the ninth
# parted at a gap of 5.5e-3 RMS (1.5e-3 in a run with other one-row
# matmul bits). TIE_MARGIN allows about 4x the larger gap, and is 20x
# below TENANT_MIN_SHIFT, the least by which (c) requires a delta to
# move the logits.
TIE_MARGIN = 0.02


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A phase's result is outside its contract."""


def require_tpu(n_chips: int):
    """The devices to run on; exits unless JAX reports ``n_chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices, JAX found {len(devs)}")
    return devs


def _rel_max(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def _rel_rms(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.sqrt(np.mean((got - ref) ** 2))
                 / max(np.sqrt(np.mean(ref ** 2)), 1e-30))


def _timed(fn, *args):
    """(compile_s, run_s, out): compile ahead of time, then one run timed
    to block_until_ready."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    return t1 - t0, t2 - t1, out


# ---------------------------------------------------------------------------
# Phase 1: kernels
# ---------------------------------------------------------------------------
def kernel_phase(cfg, *, interpret: bool = False, seed: int = 0,
                 T: int = 8) -> dict:
    """Each Pallas kernel at (h_in=d_model, h_out=d_ff) against the XLA
    fallback. Returns {kernel: {"err", "tol", "compile_s", "run_s"}}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import groupwise_dropout_pack, stack_tenant_deltas
    from repro.core.pack import reconstruct_dense
    from repro.kernels import fallback, ops
    from repro.serve.scheduler import tenant_segments

    h_in, h_out = cfg.d_model, cfg.d_ff
    h_g = ops.LANES if h_in % ops.LANES == 0 else h_in
    rng = jax.random.PRNGKey(seed)

    def pack(i):
        delta = jax.random.normal(jax.random.fold_in(rng, i), (h_in, h_out))
        return groupwise_dropout_pack(jax.random.fold_in(rng, 100 + i),
                                      delta * 0.02, h_g=h_g, alpha=8.0,
                                      k_bits=4)

    p = pack(0)
    if not ops.kernel_supported(p):
        raise SmokeFailure(f"kernel phase packing is outside the kernel "
                           f"envelope: {ops.kernel_refusal(p)}")
    x = jax.random.normal(jax.random.fold_in(rng, 1), (T, h_in))
    w = (jax.random.normal(jax.random.fold_in(rng, 2), (h_in, h_out))
         / np.sqrt(h_in)).astype(jnp.bfloat16)
    stk = stack_tenant_deltas([{"w": pack(i)} for i in range(3)])["w"]
    rows = np.array([1, 2, 1, 0, 2, 2, 1, 0][:T], np.int32)
    seg = jax.tree.map(jnp.asarray, tenant_segments(rows))
    xs = jnp.take(x, seg.order, axis=0)

    # deltas ride as arguments, not as constants baked into the program
    sr, so = seg.seg_rows, seg.seg_offsets
    cases = {
        "delta_spmm": (
            lambda x, p: ops.delta_spmm(x, p, interpret=interpret),
            lambda x, p: fallback.correction_nd(x, p), (x, p), KERNEL_TOL),
        "fused_base_delta": (
            lambda x, w, p: ops.fused_base_delta(x, w, p,
                                                 interpret=interpret),
            lambda x, w, p: x @ w.astype(jnp.float32)
            + fallback.correction_nd(x, p), (x, w, p), KERNEL_TOL),
        "delta_spmm_segments": (
            lambda xs, stk, sr, so: ops.delta_spmm_segments(
                xs, stk, sr, so, interpret=interpret),
            fallback.segment_correction, (xs, stk, sr, so), KERNEL_TOL),
        "dequant": (
            lambda p: ops.dequant(p, interpret=interpret),
            reconstruct_dense, (p,), DEQUANT_TOL),
    }
    out = {}
    for name, (kern, ref, args, tol) in cases.items():
        compile_s, run_s, got = _timed(kern, *args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        err = _rel_max(got, want)
        out[name] = {"err": err, "tol": tol, "compile_s": compile_s,
                     "run_s": run_s}
        log(f"kernel {name} h_in={h_in} h_out={h_out} h_g={h_g} "
            f"keep={p.keep} k_bits=4 T={T}: max|kernel-xla|/max|xla| "
            f"{err:.3e} (tol {tol:g}), compile {compile_s:.3f}s, "
            f"run {run_s * 1e3:.3f}ms")
        if not err <= tol:
            raise SmokeFailure(f"kernel {name}: error {err:.3e} > {tol:g}")
    return out


# ---------------------------------------------------------------------------
# Model, tenants and the request stream
# ---------------------------------------------------------------------------
def cut_depth(cfg, n_layers: int):
    """``cfg`` with its first ``n_layers`` layers (at most all of them)."""
    if n_layers >= cfg.n_layers:
        return cfg
    return cfg.replace(n_layers=n_layers,
                       layer_kinds=cfg.layer_kinds[:n_layers],
                       layer_windows=cfg.layer_windows[:n_layers])


def build(cfg, seed: int = 0):
    """(base params, [(name, deltas, report)]) — random weights from seed,
    tenants synthesised and compressed as the serve launcher does."""
    import jax
    from repro.launch.serve import RATIO_SPECS, synth_tenants
    from repro.models import lm
    rng = jax.random.PRNGKey(seed)
    base = lm.init_params(cfg, rng)
    tenants = synth_tenants(cfg, base, len(RATIOS),
                            [RATIO_SPECS[r] for r in RATIOS], rng)
    return base, tenants


def make_stream(cfg, names, seed: int = 0) -> list:
    """Two requests per tenant plus one base-model request (tenant None),
    PROMPT_LEN random tokens each, from the seed."""
    import jax
    import numpy as np
    rng = jax.random.PRNGKey(seed + 1)
    owners = [n for n in names for _ in range(2)]
    owners.insert(len(owners) // 2, None)
    stream = []
    for i, who in enumerate(owners):
        prompt = np.asarray(jax.random.randint(
            jax.random.fold_in(rng, i), (PROMPT_LEN,), 0, cfg.vocab),
            np.int32)
        stream.append((who, prompt))
    return stream


def serve(cfg, base, tenants, stream, *, max_new: int = MAX_NEW,
          mesh=None, engine=None):
    """Serve ``stream`` through a ContinuousEngine (a new one unless
    ``engine`` is given); fails unless every request finished with
    ``max_new`` tokens. Returns (engine, requests, wall seconds)."""
    from repro.serve import ContinuousEngine
    eng = engine
    if eng is None:
        eng = ContinuousEngine(cfg, base, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                               mesh=mesh)
        for name, deltas, report in tenants:
            eng.register_tenant(name, deltas, report)
    t0 = time.perf_counter()
    reqs = [eng.submit(who, prompt, max_new_tokens=max_new)
            for who, prompt in stream]
    eng.run()
    wall = time.perf_counter() - t0
    short = [(r.rid, len(r.output())) for r in reqs
             if not r.done or len(r.output()) != max_new]
    if short:
        raise SmokeFailure(f"requests not done with {max_new} tokens "
                           f"(rid, tokens): {short}")
    return eng, reqs, wall


def serving_phase(cfg, base, tenants, stream, *, max_new: int = MAX_NEW,
                  warm: bool = True):
    """Serve the stream on one engine: the first pass compiles each
    program at its first step; with ``warm`` a second pass runs them
    warm. Each pass ends when its tokens reached the host. Returns
    (engine, first requests)."""
    eng, reqs, first_s = serve(cfg, base, tenants, stream, max_new=max_new)
    msg = f"first pass {first_s:.3f}s"
    if warm:
        _, _, warm_s = serve(cfg, base, tenants, stream, max_new=max_new,
                             engine=eng)
        msg += (f", warm pass {warm_s:.3f}s, so compiles "
                f"~{first_s - warm_s:.3f}s")
    rep = eng.metrics.report()
    paths = rep["decode_paths"] or {}
    log(f"serve: {len(reqs)} requests x {max_new} tokens done; {msg} "
        f"(host clock)")
    log(f"serve: decode_paths {paths}")
    if not paths or "unknown" in paths:
        raise SmokeFailure(f"decode steps carry no path attribution: {paths}")
    return eng, reqs


def reference_engine(cfg, base, tenants):
    """The static per-tenant Engine: the serving path's reference."""
    from repro.serve import Engine
    ref = Engine(cfg, base, max_seq=MAX_SEQ)
    for name, deltas, report in tenants:
        ref.register_tenant(name, deltas, report)
    return ref


def _next_logits(ref, who, prompt, tokens):
    """``ref``'s logits for the token after ``prompt + tokens``, feeding
    ``tokens`` one decode step at a time as Engine.generate does."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.apply import set_mesh
    from repro.models import lm
    set_mesh(None)
    deltas = ref.store.get(who).deltas if who else None
    cache = lm.init_cache(ref.cfg, 1, ref.max_seq)
    lg, cache = ref._prefill(ref.base, {"tokens": jnp.asarray(prompt[None])},
                             cache, deltas)
    for i, t in enumerate(tokens):
        lg, cache = ref._decode(ref.base, cache, jnp.asarray([[t]], jnp.int32),
                                jnp.int32(len(prompt) + i), deltas)
    return np.asarray(lg[0], np.float64)


def check_tokens(ref, stream, got, want, label: str) -> dict:
    """Each request's tokens ``got`` against ``want``: equal, or else, at
    the first token where they part, ``got``'s token lies within
    TIE_MARGIN * RMS of the best logit ``ref`` gives there (teacher-forced
    on the common prefix). Returns {"exact": n, "diverged": [...]}."""
    import numpy as np
    exact, diverged = 0, []
    for (who, prompt), g, w in zip(stream, got, want):
        if np.array_equal(g, w):
            exact += 1
            continue
        k = int(np.argmax(g != w))
        lg = _next_logits(ref, who, prompt, list(w[:k]))
        gap = float((lg.max() - lg[g[k]]) / np.sqrt(np.mean(lg ** 2)))
        diverged.append({"tenant": who, "token": k, "gap": gap})
    log(f"{label}: {exact}/{len(got)} requests token-identical; parted "
        f"(tenant, token index, gap/RMS): "
        f"{[(d['tenant'], d['token'], d['gap']) for d in diverged]}")
    far = [d for d in diverged if not d["gap"] <= TIE_MARGIN]
    if far:
        raise SmokeFailure(
            f"{label}: {len(far)} request(s) part at a token further than "
            f"the tie margin {TIE_MARGIN:g} RMS below the reference's best, "
            f"not a near-tie: {far}")
    return {"exact": exact, "diverged": diverged}


def identity_phase(cfg, base, tenants, stream, reqs, *,
                   max_new: int = MAX_NEW):
    """(a) Each request's tokens against per-tenant Engine.generate's
    (check_tokens). Returns (reference Engine, check_tokens result)."""
    t0 = time.perf_counter()
    ref = reference_engine(cfg, base, tenants)
    want = [ref.generate(who, prompt[None], max_new_tokens=max_new)[0]
            for who, prompt in stream]
    log(f"identity: Engine.generate served {len(want)} requests in "
        f"{time.perf_counter() - t0:.3f}s (compiles included)")
    return ref, check_tokens(ref, stream, [r.output() for r in reqs], want,
                             "identity vs Engine.generate")


def _merged_f32(params, deltas):
    """float32 base + reconstruct_dense(delta), leaf by leaf."""
    import jax.numpy as jnp
    from repro.core.pack import reconstruct_dense
    if isinstance(params, dict):
        return {k: _merged_f32(v, deltas.get(k) if isinstance(deltas, dict)
                               else None) for k, v in params.items()}
    p = params.astype(jnp.float32)
    return p if deltas is None else p + reconstruct_dense(deltas)


def logits_phase(cfg, ref, tenants, stream) -> dict:
    """(b) Prefill + decode logits with each tenant's packed deltas,
    through the jitted programs ``Engine.generate`` runs (``ref``, from
    identity_phase), against float32 lm.forward on base + dense delta,
    on the tenant's first prompt in the stream; (c) each tenant's logits
    differ from the base's on that prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm

    with jax.default_matmul_precision("highest"):
        forward = jax.jit(lambda p, t: lm.forward(cfg, p, {"tokens": t}))

    def served(deltas, prompt):
        """Prefill, then one decode step fed the argmax token."""
        L = prompt.shape[1]
        cache = lm.init_cache(cfg, 1, ref.max_seq)
        lp, cache = ref._prefill(ref.base, {"tokens": jnp.asarray(prompt)},
                                 cache, deltas)
        tok = jnp.argmax(lp, axis=-1).astype(jnp.int32)
        ld, _ = ref._decode(ref.base, cache, tok[:, None], jnp.int32(L),
                            deltas)
        return np.asarray(lp[0]), np.asarray(ld[0]), int(tok[0])

    out = {}
    for name, _, _ in tenants:
        prompt = next(p for who, p in stream if who == name)[None]
        L = prompt.shape[1]
        base_p, _, _ = served(None, prompt)
        deltas = ref.store.get(name).deltas      # runtime PackedDelta tree
        lp, ld, tok = served(deltas, prompt)
        merged = _merged_f32(ref.base, deltas)
        seq = jnp.asarray(np.concatenate([prompt, [[tok]]], axis=1))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(forward(merged, seq)[0])
        del merged
        gc.collect()
        e_p, e_d = _rel_rms(lp, want[L - 1]), _rel_rms(ld, want[L])
        shift = _rel_rms(lp, base_p)
        out[name] = {"prefill_err": e_p, "decode_err": e_d,
                     "prefill_max_err": _rel_max(lp, want[L - 1]),
                     "decode_max_err": _rel_max(ld, want[L]),
                     "shift_vs_base": shift}
        log(f"logits {name}: RMS(engine-f32 ref)/RMS(ref) prefill {e_p:.3e} "
            f"decode {e_d:.3e} (tol {LOGIT_TOL:g}); max-abs rel prefill "
            f"{out[name]['prefill_max_err']:.3e} decode "
            f"{out[name]['decode_max_err']:.3e}; tenant vs base "
            f"{shift:.3e} (min {TENANT_MIN_SHIFT:g})")
        if not (e_p <= LOGIT_TOL and e_d <= LOGIT_TOL):
            raise SmokeFailure(f"{name}: logits off the float32 reference "
                               f"(prefill {e_p:.3e}, decode {e_d:.3e})")
        if not shift >= TENANT_MIN_SHIFT:
            raise SmokeFailure(f"{name}: logits moved only {shift:.3e} from "
                               f"the base's: the delta was not applied")
    return out


def mesh_phase(cfg, base, tenants, stream, *, n_devices: int = 4,
               max_new: int = MAX_NEW) -> list:
    """The same stream on (data=1, model=n) and (data=2, model=n/2)
    meshes, each checked against a single-device engine (check_tokens)."""
    from repro.launch.mesh import make_serving_mesh
    _, ref_reqs, ref_s = serve(cfg, base, tenants, stream, max_new=max_new)
    want = [r.output() for r in ref_reqs]
    log(f"mesh: single-device reference served {len(want)} requests in "
        f"{ref_s:.3f}s (compiles included)")
    ref = reference_engine(cfg, base, tenants)
    shapes = []
    for data in (1, 2):
        mesh = make_serving_mesh(n_devices, data=data)
        eng, reqs, wall = serve(cfg, base, tenants, stream, max_new=max_new,
                                mesh=mesh)
        shape = dict(mesh.shape)
        paths = eng.metrics.report()["decode_paths"]
        log(f"mesh {shape}: {len(reqs)} requests in {wall:.3f}s (compiles "
            f"included); decode_paths {paths}")
        del eng
        gc.collect()
        check_tokens(ref, stream, [r.output() for r in reqs], want,
                     f"mesh {shape} vs single device")
        shapes.append(shape)
    return shapes


# ---------------------------------------------------------------------------
def _peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use'] / 1e9:.3f} GB"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, serving and correctness on one chip; "
                         "4: only the mesh path, against one device")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=LAYERS,
                    help=f"model depth (default {LAYERS}; the model has 16)")
    ap.add_argument("--no-warm", dest="warm", action="store_false",
                    help="serve the stream once, without the warm pass")
    args = ap.parse_args(argv)

    try:
        import repro  # noqa: F401  (the program this smoke drives)
    except ImportError as e:
        raise SystemExit(f"chip_smoke: the repro package is not under "
                         f"{SRC}: {e}")
    from repro.configs import get_config
    from repro.utils import enable_compile_cache, tree_bytes

    devs = require_tpu(args.chips)
    cache_dir = enable_compile_cache()
    dev = devs[0]
    log(f"device_kind {dev.device_kind!r}, platform {dev.platform}, "
        f"{len(devs)} device(s); compile cache {cache_dir}")
    cfg = cut_depth(get_config(ARCH), args.layers)

    if args.chips == 1:
        kernel_phase(cfg, seed=args.seed)
    t0 = time.perf_counter()
    base, tenants = build(cfg, args.seed)
    delta_bytes = sum(tree_bytes(d) for _, d, _ in tenants)
    log(f"model {cfg.name} ({cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}): params "
        f"{tree_bytes(base) / 1e9:.3f} GB; "
        f"{len(tenants)} tenants at ratios {RATIOS}: packed deltas "
        f"{delta_bytes / 1e9:.4f} GB; built in "
        f"{time.perf_counter() - t0:.1f}s")
    for name, _, report in tenants:
        log(f"  {name}: {report.summary()}")
    stream = make_stream(cfg, [n for n, _, _ in tenants], args.seed)

    if args.chips == 4:
        mesh_phase(cfg, base, tenants, stream, n_devices=4)
    else:
        eng, reqs = serving_phase(cfg, base, tenants, stream, warm=args.warm)
        del eng
        ref, _ = identity_phase(cfg, base, tenants, stream, reqs)
        t0 = time.perf_counter()
        logits_phase(cfg, ref, tenants, stream)
        log(f"logits phase {time.perf_counter() - t0:.3f}s (compiles "
            f"included)")
    log(f"peak_bytes_in_use {_peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
