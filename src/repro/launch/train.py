"""Production training launcher.

Ties together: arch configs, mesh + sharding rules (FSDP/TP/ZeRO-1),
deterministic data, AdamW, microbatching, optional int8 compressed gradient
all-reduce, periodic async checkpointing and crash-restart resume.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --steps 100 --batch 8 --seq 128 --data 2 --model 1 \
        --ckpt-dir /tmp/run1 [--resume] [--grad-compress]

On the CPU container this runs the smoke config by default; pass
``--full`` to use the production config (real-cluster usage).
"""
import argparse
import time

import jax

from repro.checkpoint import Checkpointer
from repro.configs import get_config, get_smoke_config
from repro.data import PretrainMixture
from repro.dist import ShardingRules, tree_shardings, zero1_shardings
from repro.dist.sharding import TRAIN_OVERRIDES
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.optim import adamw, schedule
from repro.optim.adamw import AdamWConfig
from repro.train import make_train_step
from repro.utils import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--full", action="store_true", help="production config (not smoke)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--data", type=int, default=1, help="data-parallel mesh size")
    ap.add_argument("--model", type=int, default=1, help="model-parallel mesh size")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compress", action="store_true",
                    help="int8 error-feedback compressed DP all-reduce")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    mesh = make_mesh((args.data, args.model), ("data", "model"))
    rules = ShardingRules(mesh).with_overrides(**TRAIN_OVERRIDES)

    p_specs, p_axes = lm.param_specs(cfg), lm.param_axes(cfg)
    p_sh = tree_shardings(rules, p_specs, p_axes)
    o_sh = {
        "m": zero1_shardings(rules, p_specs, p_axes),
        "v": zero1_shardings(rules, p_specs, p_axes),
        "master": zero1_shardings(rules, p_specs, p_axes),
        "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    }

    data = PretrainMixture(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch)
    opt_cfg = AdamWConfig(lr=args.lr,
                          schedule=schedule.cosine_with_warmup(
                              max(args.steps // 20, 1), args.steps))
    grad_transform = None
    if args.grad_compress and args.data > 1:
        from repro.dist import make_compressed_allreduce
        grad_transform = make_compressed_allreduce(mesh, "data")
    step_fn = make_train_step(cfg, opt_cfg, n_micro=args.n_micro, remat=True,
                              grad_transform=grad_transform)

    with mesh:
        params = jax.tree.map(lambda a, s: jax.device_put(a, s),
                              lm.init_params(cfg, jax.random.PRNGKey(0)), p_sh)
        # moments/master inherit the param layout at init; re-place them on
        # the ZeRO-1 layout (data-sharded free dims) the jit expects
        opt = jax.tree.map(lambda a, s: jax.device_put(a, s),
                           adamw.init(params), o_sh)
        start = 0
        ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        if args.resume and ck and ck.latest_step() is not None:
            state, man = ck.restore({"params": params, "opt": opt},
                                    shardings={"params": p_sh, "opt": o_sh})
            params, opt, start = state["params"], state["opt"], man["extra"]["data_step"]
            print(f"resumed from step {start}")

        # pin outputs too: params/opt must round-trip on their layouts, or
        # step i+1 sees different committed shardings than step i
        jf = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None, None),
                     out_shardings=(p_sh, o_sh, None))
        t0 = time.time()
        tokens = 0
        for i in range(start, args.steps):
            params, opt, m = jf(params, opt, data.batch_at(i), jax.random.PRNGKey(i))
            tokens += args.batch * args.seq
            if i % args.log_every == 0 or i == args.steps - 1:
                dt = time.time() - t0
                print(f"step {i:5d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.3f} lr {float(m['lr']):.2e} "
                      f"tok/s {tokens / max(dt, 1e-9):.0f}", flush=True)
            if ck and (i + 1) % args.ckpt_every == 0:
                ck.save(i + 1, {"params": params, "opt": opt},
                        extra={"data_step": i + 1}, blocking=False)
        if ck:
            ck.wait()
            ck.save(args.steps, {"params": params, "opt": opt},
                    extra={"data_step": args.steps})
    print("done")


if __name__ == "__main__":
    main()
