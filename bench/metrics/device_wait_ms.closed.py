"""Plans and kernels, as the host sees them: mean time from a dispatch's
plan call to its `block_until_ready` returning (the program's
`dispatch.device` spans), over the window less its profiled part."""
from benchlib import spans


def read(ctx):
    return spans.mean_ms(ctx, "dispatch.device")
