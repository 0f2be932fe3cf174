"""Single derivation layer for the paper's metric triple.

The paper's headline claims are throughput **and** fairness **and**
energy efficiency (6.5×/7.1× for Colibri vs LRSC at high contention),
so every simulation result — not just the figure-specific scripts —
must report all three.  This module owns that derivation:

* **Throughput** — completed ops per cycle (plus the Fig. 5 worker
  streaming rate), exactly as the engine always reported it.
* **Fairness** — Jain's fairness index over the per-core completed-op
  distribution (1.0 = perfectly uniform, 1/n = one core monopolises),
  alongside the legacy min/max rates and a NaN-safe span.  The raw
  ``fairness_max / max(fairness_min, 1e-9)`` span the benchmarks used
  to compute blows up to ~1e9 the moment any core completes 0 ops;
  Jain's index is bounded in (0, 1] and degrades smoothly, and
  :func:`fairness_span` pins the starved case to ``inf`` explicitly
  (with :func:`json_safe` mapping it to ``None`` for reports).
* **Latency** — per-atomic completion-latency percentiles (p50 / p95 /
  max), measured from the cycle a core first issues an acquire to the
  cycle the micro-op retires (so retry storms, backoff loops and queue
  waits all count).  The engine always accumulates a geometric
  latency histogram (``lat_hist``, :data:`LAT_BINS` buckets with
  :data:`LAT_SUB` sub-buckets per octave → ≤ ~19 % value resolution)
  plus the exact maximum (``lat_max``); when a full completion trace is
  recorded (``record_trace=True`` → ``trace_wait``) the percentiles are
  exact instead of bucketed.
* **Energy** — pJ per completed op through the Table II-calibrated
  event-energy model (``core.costmodel``), threaded through ``run()``
  and ``sweep()`` so every result dict carries ``energy_pj_per_op``.

Degenerate configurations (``n_workers == n_cores`` leaves no atomic
cores; zero completions) consistently report 0.0 instead of crashing.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

from repro.core import costmodel

#: latency histogram geometry: bucket(v) = floor(LAT_SUB * log2(v + 1)),
#: clipped to [0, LAT_BINS).  64 buckets at 4 sub-buckets per octave
#: cover latencies up to 2^16 cycles at ≤ 2^(1/4) ≈ 1.19× bucket width.
LAT_BINS = 64
LAT_SUB = 4

#: engine stat totals the energy model bills (see costmodel.fit_energy)
ENERGY_STAT_KEYS = ("msgs", "bank_ops", "active_cyc", "sleep_cyc",
                    "backoff_cyc", "bar_cyc")

#: the triple every result dict must carry (schema-checked in reports)
METRIC_TRIPLE = ("jain_fairness", "lat_p95", "energy_pj_per_op")


def json_safe(v: float) -> Optional[float]:
    """Map non-finite metric values (inf span from a starved core, NaN)
    to ``None`` so benchmark report rows stay strict JSON."""
    return float(v) if math.isfinite(v) else None


# ---------------------------------------------------------------------------
# Fairness
# ---------------------------------------------------------------------------

def jain_fairness(ops) -> float:
    """Jain's fairness index ``(Σx)² / (n·Σx²)`` over per-core ops.

    1.0 when every core completed the same count; → 1/n when a single
    core monopolises; 0.0 for an empty slice or when nothing completed
    (no allocation to be fair about).
    """
    x = np.asarray(ops, dtype=np.float64).ravel()
    if x.size == 0:
        return 0.0
    sq = float((x * x).sum())
    if sq == 0.0:
        return 0.0
    return float(x.sum()) ** 2 / (x.size * sq)


def fairness_span(ops) -> float:
    """NaN-safe fastest/slowest per-core ops ratio: ``inf`` when some
    core starved (0 ops) while another made progress, 0.0 when nothing
    completed at all (or the slice is empty) — never a division by an
    epsilon that manufactures a ~1e9 pseudo-value."""
    x = np.asarray(ops, dtype=np.float64).ravel()
    if x.size == 0:
        return 0.0
    lo, hi = float(x.min()), float(x.max())
    if lo <= 0.0:
        return 0.0 if hi <= 0.0 else math.inf
    return hi / lo


# ---------------------------------------------------------------------------
# Latency percentiles
# ---------------------------------------------------------------------------

def bucket_rep(i) -> np.ndarray:
    """Representative latency for histogram bucket ``i`` (geometric mean
    of the bucket's value range ``[2^(i/S) - 1, 2^((i+1)/S) - 1)``)."""
    return np.power(2.0, (np.asarray(i, np.float64) + 0.5) / LAT_SUB) - 1.0


def _percentile_from_hist(hist: np.ndarray, q: float,
                          lat_max: float) -> float:
    """Inverted-CDF percentile from the geometric histogram, clamped to
    the exact observed maximum."""
    cum = np.cumsum(hist.astype(np.int64))
    total = int(cum[-1]) if cum.size else 0
    if total == 0:
        return 0.0
    want = max(int(math.ceil(q * total)), 1)
    idx = int(np.searchsorted(cum, want))
    return float(min(bucket_rep(idx), lat_max))


def _percentile_from_waits(waits: np.ndarray, q: float) -> float:
    """Exact inverted-CDF percentile (the value at rank ⌈q·k⌉) over the
    recorded per-completion waits."""
    if waits.size == 0:
        return 0.0
    s = np.sort(waits)
    return float(s[max(int(math.ceil(q * s.size)), 1) - 1])


def trace_latency_hist(res: Dict[str, np.ndarray],
                       interpret: Optional[bool] = None) -> np.ndarray:
    """Exact-trace completion-latency histogram on the engine's geometric
    bins — the recorded per-completion waits (``record_trace=True``)
    folded onto the same ``LAT_BINS``/``LAT_SUB`` geometry as the
    always-on ``lat_hist`` accumulator, so the two are directly
    comparable (equal, in fact: both count every retirement once —
    ``tests/test_kernels.py`` pins this against a live engine run).

    With ``interpret`` given, the commit goes through the
    ``colibri_scatter`` Pallas kernel (the paper's retry-free scatter-RMW
    counting its own latencies), interpreted or compiled as it says;
    ``None`` uses a plain ``np.bincount``.
    """
    tw = np.asarray(res["trace_wait"])
    waits = tw[tw >= 0]
    if waits.size == 0:
        return np.zeros((LAT_BINS,), np.int32)
    # identical bucket math to the engine's in-scan accumulator,
    # including the float32 rounding
    bkt = np.clip((LAT_SUB * np.log2(
        waits.astype(np.float32) + np.float32(1.0))).astype(np.int32),
        0, LAT_BINS - 1)
    if interpret is None:
        return np.bincount(bkt, minlength=LAT_BINS).astype(np.int32)
    from repro.kernels.colibri_scatter import colibri_histogram
    return np.asarray(colibri_histogram(bkt, LAT_BINS, interpret=interpret))


def latency_percentiles(res: Dict[str, np.ndarray]) -> Dict[str, float]:
    """p50/p95/max completion latency for one result dict.

    Prefers the exact per-completion waits when a trace was recorded
    (``trace_wait``); otherwise reconstructs from the always-on
    ``lat_hist``/``lat_max`` accumulators (≤ one bucket width of error,
    max is exact either way).
    """
    lat_max = float(np.asarray(res.get("lat_max", 0)))
    if "trace_wait" in res:
        tw = np.asarray(res["trace_wait"])
        waits = tw[tw >= 0]
        out = {"lat_p50": _percentile_from_waits(waits, 0.50),
               "lat_p95": _percentile_from_waits(waits, 0.95)}
    else:
        hist = np.asarray(res.get("lat_hist", np.zeros(LAT_BINS, np.int64)))
        out = {"lat_p50": _percentile_from_hist(hist, 0.50, lat_max),
               "lat_p95": _percentile_from_hist(hist, 0.95, lat_max)}
    out["lat_max"] = lat_max
    return out


# ---------------------------------------------------------------------------
# Energy
# ---------------------------------------------------------------------------

def energy_stats(res: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The billable stat totals of one result dict, as plain floats —
    the exact contract :func:`costmodel.fit_energy` /
    :func:`costmodel.energy_per_op` validate."""
    s = {k: float(np.asarray(res[k])) for k in ENERGY_STAT_KEYS}
    s["ops"] = float(np.asarray(res["ops"]).sum())
    # hierarchical-topology runs carry NoC hop traversals; flat runs
    # don't have the key and the energy model bills them as before
    if "hops" in res:
        s["hops"] = float(np.asarray(res["hops"]))
    return s


# ---------------------------------------------------------------------------
# The derivation layer
# ---------------------------------------------------------------------------

def attach(res: Dict[str, np.ndarray], n_workers: int, cycles: int,
           fit: Optional[costmodel.EnergyFit] = None
           ) -> Dict[str, np.ndarray]:
    """Attach the full paper-metric set to a raw engine result dict.

    This is the single derivation layer behind ``sim.run`` /
    ``sim.derive_metrics`` and every ``sweep()`` point: throughput and
    worker rate, the per-core fairness family (min/max rates, Jain
    index, NaN-safe span), completion-latency percentiles, and pJ per
    op through ``fit`` (default: the Table II calibration,
    :func:`costmodel.default_fit`).
    """
    ops = res["ops"][n_workers:] if n_workers else res["ops"]
    res["throughput"] = float(ops.sum()) / cycles if ops.size else 0.0
    res["fairness_min"] = float(ops.min()) / cycles if ops.size else 0.0
    res["fairness_max"] = float(ops.max()) / cycles if ops.size else 0.0
    res["jain_fairness"] = jain_fairness(ops)
    res["fairness_span"] = fairness_span(ops)
    res.update(latency_percentiles(res))
    stats = energy_stats(res)
    res["energy_pj_per_op"] = (
        costmodel.energy_per_op(stats, fit or costmodel.default_fit())
        if stats["ops"] > 0 else 0.0)
    if n_workers:
        w = res["w_served"][:n_workers]
        res["worker_rate"] = (float(w.sum()) / cycles / n_workers
                              if w.size else 0.0)
    if "dead_mask" in res:
        # graceful-degradation metrics (repro.faults): the engine only
        # emits these keys when a FaultPlan is enabled, so faults-off
        # results carry zero extra columns
        dm = np.asarray(res["dead_mask"])[n_workers:] if n_workers \
            else np.asarray(res["dead_mask"])
        res["stalled_cores"] = int(np.asarray(res["dead_mask"]).sum())
        surv = ops[~dm] if dm.size else ops
        res["survivor_throughput"] = (float(surv.sum()) / cycles
                                      if surv.size else 0.0)
        res["survivor_jain"] = jain_fairness(surv)
        res["faults_injected"] = int(np.asarray(res["faults_injected"]))
        res["recoveries"] = int(np.asarray(res.get("recoveries", 0)))
        # liveness verdict: the forward-progress watchdog never flagged
        # a halt => the system kept retiring ops to the horizon
        res["halt_cyc"] = int(np.asarray(res["halt_cyc"]))
        res["progress_ok"] = bool(res["halt_cyc"] < 0)
    return res
