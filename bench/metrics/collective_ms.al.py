"""Device ms per round of cross-chip operations (all-reduce, all-gather,
collective-permute, all-to-all, reduce-scatter, and their -start/-done
halves), summed over the window's ops, averaged over chips.  None where
no such operation ran."""
import re

import reduce

COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|collective-permute|"
                        r"all-to-all|reduce-scatter)(-start|-done)?"
                        r"(\.\d+)?(\s|$)")


def read(ctx):
    rounds = ctx["counters"].get("rounds")
    ops = [e for e in reduce.clip(ctx["events"]["ops"], ctx["lo"], ctx["hi"])
           if COLLECTIVE.match(e[0])]
    if not rounds or not ops:
        return None
    ns = sum(e[2] - e[1] for e in ops)
    return ns * 1e-6 / ctx["chips"] / rounds
