"""Model step: model FLOPs of the prompts prefilled in the window, per
second of the window, as a share of the chip's peak bf16 FLOP/s, %."""
from chipbench import costs


def read(run):
    flops = sum(costs.prompt_flops(run.arch, L)
                for s in run.window.steps for _, L in s.prefills)
    if not flops:
        return None
    return 100.0 * flops / run.window.seconds / run.peaks["bf16_flops"]
