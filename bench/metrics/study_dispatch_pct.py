"""Share of the window the Study host path spent dispatching chunks:
the sum of ``obs.RunReport`` chunk dispatch wall time (tracing and
compiling on a miss, enqueueing on a hit) over the window."""


def read(trace, record, cell):
    if record["mode"] != "study" or not record["window_s"]:
        return None
    return 100.0 * record["dispatch_s"] / record["window_s"]
