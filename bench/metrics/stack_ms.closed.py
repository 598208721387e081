"""Device stage, host staging: mean time a dispatch spends stacking its
slots' padded graphs on the host (`stack_padded`; the program's
`dispatch.stack` spans), over the window less its profiled part."""
from benchlib import spans


def read(ctx):
    return spans.mean_ms(ctx, "dispatch.stack")
