"""The work of one simulated cycle, counted from shapes, and the peaks.

``bytes_per_cycle`` counts the int32 lanes the bank-side step of one
simulated cycle (arbitration, the protocol's bank update, the latency
histogram) must read or write once: the per-core request lanes it
reads, every bank-state row it reads and writes back, the per-bank
outcome lanes it writes, and the histogram and its scalars.  A bank
state that holds a queue per row counts one slot per row: the step
touches only the slot it enqueues into or pops.  The count is fixed
here, so it does not change with how a kernel carries out the step.
"""
from __future__ import annotations

import json
import os

INT32 = 4
LAT_BINS = 64
#: per-core lanes the step reads: arrival stamp, rotated priority,
#: target bank, phase, first-request stamp
CORE_LANES = 5
#: per-bank lanes it writes: valid, winner, outcome, response timer
OUT_LANES = 4
#: scalars beside the histogram: polls, messages, worst latency
STAT_LANES = 3


def bank_rows(protocol: str, n_cores: int, n_groups: int = 4) -> int:
    """Bank-state rows per bank the protocol's update reads and writes."""
    if protocol in ("colibri", "lrscwait", "mwait_lock"):
        return 4             # queue slot, head, length, wake timer
    if protocol == "colibri_hier":
        g = max(1, min(n_groups, n_cores))
        # per group: queue slot, head, length; per bank: group-queue
        # slot, its head and length, in-queue flag, holding group, ops
        # this turn, wake timer, group to wake
        return 3 * g + 8
    if protocol == "lrsc":
        return 2             # reserving core, reservation valid
    if protocol == "amo_lock":
        return 1             # lock bit
    raise ValueError(f"no bank-state count for protocol {protocol!r}")


def bytes_per_cycle(protocol: str, n_cores: int, banks: int,
                    n_groups: int = 4) -> int:
    lanes = (CORE_LANES * n_cores
             + 2 * bank_rows(protocol, n_cores, n_groups) * banks
             + OUT_LANES * banks + LAT_BINS + STAT_LANES)
    return INT32 * lanes


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {', '.join(table)}")
    return table[device_kind]


def least_seconds_per_cycle(protocol: str, n_cores: int, banks: int,
                            device_kind: str, n_groups: int = 4) -> float:
    """Least time of one cycle's bank-side step: its bytes over HBM
    bandwidth (the step does integer compares and selects, no
    floating-point work to bound it)."""
    return (bytes_per_cycle(protocol, n_cores, banks, n_groups)
            / peaks(device_kind)["hbm_bytes_per_s"])
