"""Device stage, transfer: megabytes (10^6 B) a dispatch sends to the
device per real request it answers (the `bytes` of the program's
`dispatch.h2d` spans over their `filled`, the requests they carried), over
the window less its profiled part. Junk slots are sent but answer
nothing, so a partial batch reads higher."""
from benchlib import spans


def read(ctx):
    sent = spans.bytes_per_request(ctx, "dispatch.h2d")
    return sent / 1e6 if sent is not None else None
