"""Device-idle ms per round inside the program's ``repro.fetch`` spans
(one per blocking device-to-host read of the scan query path).  It prices
the synchronous reads."""
import spans


def read(ctx):
    return spans.exposed_ms(ctx, "repro.fetch")
