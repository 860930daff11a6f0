"""Model step: decode tokens per second times model FLOPs per token
(attention over each token's live context), as a share of the chip's
peak bf16 FLOP/s, %."""
from chipbench import costs


def read(run):
    flops = sum(costs.flops_per_token(run.arch, ctx)
                for s in run.window.steps for _, ctx in s.decode_rows)
    if not flops:
        return None
    return 100.0 * flops / run.window.seconds / run.peaks["bf16_flops"]
