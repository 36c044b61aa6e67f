"""Device-idle ms per round inside the program's ``repro.query`` spans
(each a whole ``HashQueryService.query_batch`` call): the union of the
spans in the window less the device's busy intervals, over rounds.  It
prices the whole host path of the query in place."""
import spans


def read(ctx):
    return spans.exposed_ms(ctx, "repro.query")
