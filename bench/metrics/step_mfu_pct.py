"""Model FLOP utilization of the whole step: images per second times the
model's training FLOPs per image (from shapes, ``bench/flops.py``), over
the chips times their published bf16 peak (``bench/peaks.py``)."""


def read(ctx):
    if ctx.peaks is None:
        return None
    achieved = ctx.images_per_s * ctx.flops_per_image
    return 100.0 * achieved / (ctx.cell.chips * ctx.peaks["bf16_flops"])
