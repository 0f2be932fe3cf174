"""Share of the window the Study host path spent in the per-chunk
``jax.device_get`` drain (``obs.RunReport`` execute wall), over the
window."""


def read(trace, record, cell):
    if record["mode"] != "study" or not record["window_s"]:
        return None
    return 100.0 * record["drain_s"] / record["window_s"]
