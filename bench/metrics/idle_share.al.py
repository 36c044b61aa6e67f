"""Device idle share: 100 x (1 - union of device-op intervals / window)."""
import reduce


def read(ctx):
    return reduce.idle_share(ctx)
