"""Device busy time outside the engine-step kernel per simulated cycle:
the scan stages of the simulator (timers and sends, network, wake and
retire) that run as XLA operations around the kernel, over the cycles
of the whole runs traced.  Without the kernel (the XLA scan path) every
operation counts here."""


def read(trace, record, cell):
    if not trace or not trace["n_devices"] or not record["traced_cycles"]:
        return None
    return 1e6 * trace["other_s"] / record["traced_cycles"]
