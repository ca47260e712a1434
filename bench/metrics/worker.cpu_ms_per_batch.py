"""CPU milliseconds the workers' pipeline ops spent per batch they produced
in the window (the per-op CPU time of every worker, pool children's
included, from the ops' profile).

A pool child sends its op stats at most every 0.2 s, also while it waits
for credit, so a child that the consumer holds back still reports; where
the workers produced no batch in the window there is nothing to read."""


def read(run):
    c = run["counters"]
    if c["worker_batches"] <= 0:
        return None
    return 1e3 * c["worker_cpu_s"] / c["worker_batches"]
