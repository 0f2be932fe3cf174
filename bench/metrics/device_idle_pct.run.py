"""Share of the traced window in which the chip ran no operation, in a
cell of single runs: 1 - (union of device op intervals / window)."""


def read(trace, record, cell):
    if not trace or not trace["n_devices"] or record["mode"] != "single":
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
