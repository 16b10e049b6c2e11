"""Device time per micro-batch of the collective operations (gathers,
reductions, permutes, exchanges and their async start/done halves),
averaged over the cell's chips."""

import re

COLLECTIVE = re.compile(r"(all-gather|all-reduce|collective-permute"
                        r"|all-to-all|reduce-scatter)(-start|-done)?$")


def read(r):
    if r.trace is None or not r.micro_batches:
        return None
    s = [v for k, v in r.trace["op_s"].items() if COLLECTIVE.match(k)]
    if not s:
        return None
    return 1e3 * sum(s) / r.micro_batches
