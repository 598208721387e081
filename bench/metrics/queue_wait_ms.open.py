"""Pipeline scheduler: median time from a request's submission to the
start of the dispatch that answers it (intake queue, host stage and ready
buffer; the program's `request.queue` spans), over the window less its
profiled part."""
from benchlib import spans


def read(ctx):
    return spans.median_ms(ctx, "request.queue")
