"""Device stage, transfer: mean time a dispatch spends handing its stacked
features to the device (`jnp.asarray`; the program's `dispatch.h2d`
spans), over the window less its profiled part. Host clock: it ends when
the call returns, which may be before the copy does."""
from benchlib import spans


def read(ctx):
    return spans.mean_ms(ctx, "dispatch.h2d")
