"""Model step, whole step: model FLOPs of the tokens processed in the
traced window over (the trace's window × bf16 peak), in %. The family
module counts them: ``matmul_flops_per_token`` per token plus
``attn_flops`` at each token's context (dense GQA: 2 × (layer + head
parameters) per token plus 4·ctx·H·Dh per layer)."""
from bench.metrics._device import mfu


def compute(ctx):
    return mfu(ctx)
