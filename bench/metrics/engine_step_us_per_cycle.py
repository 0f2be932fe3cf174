"""Device time of the engine-step kernel per simulated cycle: the union
of the Pallas kernel's intervals in the traced window over the cycles of
the whole runs traced.  Nothing to read where no kernel ran (the XLA
scan path)."""


def read(trace, record, cell):
    if not trace or not trace["kernel_s"] or not record["traced_cycles"]:
        return None
    return 1e6 * trace["kernel_s"] / record["traced_cycles"]
