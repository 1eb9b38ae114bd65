"""The clients' forward and backward model FLOPs per round (cohort x
batch x the family's FLOPs per sample; no pruning, quantizer, control or
eval work), times the rounds completed in the traced window, over the
window, the chips and the chip's bf16 peak, in %."""


def read(ctx):
    if not ctx.rounds:
        return None
    dep = ctx.cell.config["deployment"]
    per_round = (dep["cohort"] * dep["batch_size"]
                 * ctx.family.flops_per_sample(ctx.cell.config["model"]))
    return 100.0 * per_round * ctx.rounds / (
        ctx.trace.window_s * ctx.chips * ctx.peaks["bf16_flops"])
