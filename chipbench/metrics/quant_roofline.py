"""The stochastic quantizer kernel's share of its bandwidth roofline, in
%: 8 bytes per entry it quantizes (read the gradient, write the upload,
float32), over the summed device time of its operations, over the chip's
HBM bandwidth. The uniform draws it also reads are not counted, so the
share stays comparable across implementations. Entries: each leaf the
kernel takes (``quant_entries``), times the cohort, times the rounds
completed in the traced window."""
from chipbench import trace

# the Pallas call inside ``stochastic_quant_dyn``: an op named for that
# function (``%vmap_jit_stochastic_quant_dyn__.96`` in a TPU trace; not an
# op that merely takes its output) that bears one of the marks a Pallas
# (Mosaic) kernel leaves in a TPU trace, by its name or its op path
SCOPE = "stochastic_quant_dyn"
KERNEL_MARKS = ("pallas_call", "tpu_custom_call", "custom-call", "mosaic")


def read(ctx):
    devices = ctx.trace.devices()[:ctx.chips]
    seconds = sum(trace.op_seconds(ctx.trace, d, any_of=KERNEL_MARKS,
                                   own=(SCOPE,)) for d in devices)
    if seconds <= 0 or not ctx.rounds:
        return None
    entries = ctx.family.quant_entries(ctx.cell.config["model"])
    moved = 8.0 * entries * ctx.cell.config["deployment"]["cohort"] \
        * ctx.rounds
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / seconds
