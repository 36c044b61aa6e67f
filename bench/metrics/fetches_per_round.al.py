"""Blocking device-to-host reads per round: the program's ``repro.fetch``
spans that start in the window, over rounds."""
import spans


def read(ctx):
    return spans.calls_per_round(ctx, "repro.fetch")
