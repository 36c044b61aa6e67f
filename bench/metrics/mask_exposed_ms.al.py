"""Device-idle ms per round inside the program's ``repro.mask`` spans (the
host gather of the row mask over n rows, its upload, the gather at the
candidates and the ``&``).  It prices what a device-resident mask would
remove."""
import spans


def read(ctx):
    return spans.exposed_ms(ctx, "repro.mask")
