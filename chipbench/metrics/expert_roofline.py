"""The held experts' grouped products' share of the chip's bf16 peak, in
%: their model FLOPs (the family's ``expert_flops``: forward and backward
at the expected load, top-k of E of which the share holds its experts),
times the cohort, times the rounds completed in the traced window, over
the summed device time of the grouped products' operations, over the
chip's bf16 peak. The count is fixed by the configuration, so the share
reads the same work whatever implements it; recomputed forward passes
are not counted."""
from chipbench import trace

# the TPU compiler's kernels for ``jax.lax.ragged_dot`` (``expert_products``
# in ``repro.models.moe``): ``%ragged-dot-none.3`` and the fusions and
# metadata ops it names alike
SCOPE = "ragged-dot"


def read(ctx):
    fam = ctx.family
    if not hasattr(fam, "expert_flops") or not ctx.rounds:
        return None
    devices = ctx.trace.devices()[:ctx.chips]
    seconds = sum(trace.op_seconds(ctx.trace, d, own=(SCOPE,))
                  for d in devices)
    if seconds <= 0:
        return None
    dep = ctx.cell.config["deployment"]
    flops = fam.expert_flops(ctx.cell.config["model"], dep["batch_size"]) \
        * dep["cohort"] * ctx.rounds
    return 100.0 * flops / ctx.peaks["bf16_flops"] / seconds
