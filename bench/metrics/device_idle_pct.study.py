"""Share of the traced window in which a device ran no operation, in a
Study cell, as the mean over the cell's devices."""


def read(trace, record, cell):
    if not trace or not trace["n_devices"] or record["mode"] != "study":
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
