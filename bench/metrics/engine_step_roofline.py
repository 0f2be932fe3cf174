"""The engine-step kernel's share of its roofline, bound by HBM
bandwidth: the least time of one cycle's bank-side step (its bytes,
``work.bytes_per_cycle``, over the chip's HBM bandwidth) over the
kernel's measured device time per simulated cycle, the cycles being
those of the whole runs traced."""
import work


def read(trace, record, cell):
    if not trace or not trace["kernel_s"] or not record["traced_cycles"]:
        return None
    per_cycle = trace["kernel_s"] / record["traced_cycles"]
    least = work.least_seconds_per_cycle(
        record["protocol"], record["n_cores"], record["banks"],
        record["device_kind"], record["n_groups"])
    return 100.0 * least / per_cycle
