"""CPU milliseconds the workers' pipeline ops spent per batch they produced
in the window (the per-op CPU time of every worker, pool children's
included, from the ops' profile).

A pool child sends its op stats only after it sends an element, and at
most every 0.2 s; it runs up to 64 elements ahead and is credited 32 at a
time.  Where the consumer is the bound, as in a step-bound cell, a child
waits tens of seconds for credit and the window may see no stats at all:
nothing to read.  The metric is declared for cells whose workers are the
bound, where every element brings its stats."""


def read(run):
    c = run["counters"]
    if c["worker_batches"] <= 0:
        return None
    return 1e3 * c["worker_cpu_s"] / c["worker_batches"]
