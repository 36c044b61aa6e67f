"""Device program launches per active-learning round (every XLA module
execution in the window, over rounds completed)."""
import reduce


def read(ctx):
    rounds = ctx["counters"].get("rounds")
    n = reduce.launches(ctx["events"], ctx["lo"], ctx["hi"])
    return n / rounds if rounds and n else None
