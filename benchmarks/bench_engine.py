"""Engine hot-path throughput: simulated core-cycles/second and sweep
points/second, tracked against the pre-overhaul baseline.

Three measurements, all warm (compile excluded — the persistent
compilation cache makes repeated benchmark runs skip compiles anyway):

* **engine** — one single-point ``repro.sync.run`` at 64 / 256 / 1024
  cores, 20k cycles, reported as simulated core-cycles per wall-second.
  The 1024-core row is the run the argsort-arbitration engine made
  impractical; the headline checks it now completes under the old
  256-core wall budget.
  The full pass adds a 4096-core row — the scale target of the Pallas
  fused-step backend — checked against the same old 256-core budget.
* **unroll ablation** — the 256-core run at ``unroll`` 1 / 4 / 8
  (EXPERIMENTS.md §Engine-throughput quotes the table).
* **backend pair** — the identical 256-core Spec run on
  ``backend="xla_cpu"`` vs the Pallas path (the native ``pallas_gpu`` /
  ``pallas_tpu`` lowering when an accelerator is visible, else the
  ``pallas_interpret`` debugging path, which is expected to be slow —
  the ratio is only a perf claim on accelerator hosts; on CPU it just
  pins that the kernel path runs end-to-end).
* **grid256** — the ``workloads_grid`` study (5 workloads × 5 protocols
  × 2 seeds) at 256 cores through ``Study.run()``, reported as points
  per second.  The acceptance bar for the hot-path overhaul is ≥2×
  against ``PRE_PR`` here.
* **telemetry ablation** — the engine run with the windowed-telemetry
  knob (``repro.obs``) at windows ∈ {0, 64, 256} on both the scan and
  Pallas-interpret backends (EXPERIMENTS.md §Telemetry-cost quotes the
  table).  The acceptance bar: ``telemetry_windows=64`` costs ≤ 10%
  engine wall time at 1024 cores on both backends.

``PRE_PR`` holds the baseline measured at commit e6a3f48 (per-cycle
``jnp.argsort`` acceptance, fused int32 FIFO key, no unroll, per-key
host syncs) on the same 2-vCPU reference box that produced every other
number in EXPERIMENTS.md; ``reports/benchmarks.engine.json`` preserves
the ratio so future PRs have a perf trajectory to compare against.

``REPRO_BENCH_QUICK=1`` (the CI smoke row) trims to 64/256 cores,
2k cycles and a 2-workload grid so the row stays cheap.
"""
from __future__ import annotations

from typing import Dict, List

from benchmarks._common import QUICK, pick, time_best, time_median
from repro.core.sim import resolve_backend
from repro.sync import Spec, Study, run

#: QUICK rows gate CI through check_trend.py; median-of-N flakes far
#: less than best-of-N on the short smoke horizons (see _common)
_time = time_median if QUICK else time_best

ENGINE_CYCLES = pick(20_000, 2_000)
ENGINE_CORES = pick((64, 256, 1024, 4096), (64, 256))
UNROLLS = pick((2, 4, 8), ())              # default unroll=1 is the
GRID_CYCLES = pick(3_000, 1_000)           # engine_256c row itself
PAIR_CYCLES = pick(2_000, 500)             # backend pair: interpret-safe
GRID_WORKLOADS = pick(("rmw_loop", "ms_queue", "treiber_stack",
                       "zipf_histogram", "barrier_phases"),
                      ("rmw_loop", "ms_queue"))
GRID_PROTOS = pick(("colibri", "lrscwait", "mwait_lock", "lrsc",
                    "amo_lock"),
                   ("colibri", "lrsc"))
GRID_SEEDS = pick((0, 1), (0,))
TELE_WINDOWS = pick((0, 64, 256), (0, 64))
TELE_CORES = pick((256, 1024), (256,))
TELE_CYCLES = pick(20_000, 2_000)
TELE_INTERP_CYCLES = pick(2_000, 500)      # interpret path: shorter horizon

#: pre-overhaul baseline (commit e6a3f48), measured with this module's
#: exact protocol on the reference box.  Keys match the row labels.
PRE_PR = {
    "engine_64c": 4.235e5,      # simulated core-cycles / s, warm
    "engine_256c": 5.908e5,
    "engine_1024c": 9.050e5,
    "engine_256c_wall_s": 8.67,  # the "old 256-core budget" (20k cycles)
    "engine_1024c_wall_s": 22.63,
    "grid256_points_per_s": 0.989,  # 50-point workloads_grid sweep @256c
}


def _pallas_backend() -> str:
    """The Pallas backend this host can actually run: the native
    lowering when ``auto`` resolves to one, else the interpreter."""
    bk = resolve_backend("auto")
    return bk if bk.startswith("pallas") else "pallas_interpret"


def _grid_study() -> Study:
    from benchmarks.bench_workloads import _scenario
    return Study.from_specs(
        Spec(protocol=proto, workload=wl, n_cores=256,
             cycles=GRID_CYCLES, seed=seed, **_scenario(wl))
        for wl in GRID_WORKLOADS for proto in GRID_PROTOS
        for seed in GRID_SEEDS)


def rows() -> List[Dict]:
    bk = resolve_backend("auto")
    out: List[Dict] = []
    for n in ENGINE_CORES:
        s = Spec(protocol="colibri", n_cores=n, cycles=ENGINE_CYCLES)
        dt = _time(lambda: run(s), reps=1 if n >= 1024 else 3)
        label = f"engine_{n}c"
        out.append({"figure": "engine", "row": label, "n_cores": n,
                    "cycles": ENGINE_CYCLES, "backend": bk, "wall_s": dt,
                    "core_cycles_per_s": n * ENGINE_CYCLES / dt,
                    "pre_pr_core_cycles_per_s": PRE_PR.get(label)})
    for u in UNROLLS:
        s = Spec(protocol="colibri", n_cores=256, cycles=ENGINE_CYCLES,
                 unroll=u)
        dt = _time(lambda: run(s))
        out.append({"figure": "engine", "row": f"unroll_{u}", "n_cores": 256,
                    "cycles": ENGINE_CYCLES, "backend": bk, "wall_s": dt,
                    "core_cycles_per_s": 256 * ENGINE_CYCLES / dt})
    pb = _pallas_backend()
    s = Spec(protocol="colibri", n_cores=256, cycles=PAIR_CYCLES)
    dt_x = _time(lambda: run(s.replace(backend="xla_cpu")), reps=1)
    dt_p = _time(lambda: run(s.replace(backend=pb)), reps=1)
    out.append({"figure": "engine", "row": "backend_pair_256c",
                "n_cores": 256, "cycles": PAIR_CYCLES,
                "backend": f"xla_cpu_vs_{pb}", "wall_s": dt_x,
                "wall_s_xla": dt_x, "wall_s_pallas": dt_p,
                "pallas_over_xla": dt_p / dt_x})
    # hierarchical-topology overhead: the same engine run with the
    # cluster2 network stage on (per-level link caps + hop billing) —
    # the flat row above is the in-benchmark baseline for the cost of
    # the topology lookup
    n_topo = min(256, max(ENGINE_CORES))
    s = Spec(protocol="colibri", n_cores=n_topo, cycles=ENGINE_CYCLES,
             topology="cluster2", clusters=4)
    dt = _time(lambda: run(s))
    out.append({"figure": "engine", "row": f"engine_cluster2_{n_topo}c",
                "n_cores": n_topo, "cycles": ENGINE_CYCLES, "backend": bk,
                "topology": "cluster2", "wall_s": dt,
                "core_cycles_per_s": n_topo * ENGINE_CYCLES / dt})
    study = _grid_study()
    dt = _time(lambda: study.run(), reps=1)
    out.append({"figure": "engine", "row": "grid256", "n_points": len(study),
                "cycles": GRID_CYCLES, "backend": bk, "wall_s": dt,
                "points_per_s": len(study) / dt,
                "pre_pr_points_per_s": PRE_PR["grid256_points_per_s"]})
    # telemetry-cost ablation: windows x cores x backend (w=0 is the
    # statically-elided off path, the in-row baseline for the overhead)
    for n in TELE_CORES:
        for tele_bk, cycles, tag in ((bk, TELE_CYCLES, "tele"),
                                     (pb, TELE_INTERP_CYCLES,
                                      "tele_interp")):
            base_dt = None
            for w in TELE_WINDOWS:
                s = Spec(protocol="colibri", n_cores=n, cycles=cycles,
                         backend=tele_bk, telemetry_windows=w)
                dt = _time(lambda: run(s), reps=1 if n >= 1024 else 3)
                if w == 0:
                    base_dt = dt
                out.append({"figure": "engine", "row": f"{tag}_w{w}_{n}c",
                            "n_cores": n, "cycles": cycles,
                            "backend": tele_bk, "telemetry_windows": w,
                            "wall_s": dt,
                            "core_cycles_per_s": n * cycles / dt,
                            "overhead_vs_w0": dt / base_dt - 1.0})
    return out


def headline(rs: List[Dict]) -> Dict[str, float]:
    by = {r["row"]: r for r in rs}
    head: Dict[str, float] = {}
    e256 = by.get("engine_256c")
    if e256:
        head["engine_256c_Mcyc_per_s"] = e256["core_cycles_per_s"] / 1e6
        head["engine_256c_speedup_vs_pre_pr"] = (
            e256["core_cycles_per_s"] / PRE_PR["engine_256c"])
    e1024 = by.get("engine_1024c")
    if e1024:
        head["engine_1024c_Mcyc_per_s"] = e1024["core_cycles_per_s"] / 1e6
        head["engine_1024c_under_old_256c_budget"] = float(
            e1024["wall_s"] <= PRE_PR["engine_256c_wall_s"])
    e4096 = by.get("engine_4096c")
    if e4096:
        head["engine_4096c_Mcyc_per_s"] = e4096["core_cycles_per_s"] / 1e6
        head["engine_4096c_under_old_256c_budget"] = float(
            e4096["wall_s"] <= PRE_PR["engine_256c_wall_s"])
    pair = by.get("backend_pair_256c")
    if pair:
        head["backend_pair_pallas_over_xla"] = pair["pallas_over_xla"]
    ntopo = min(256, max(ENGINE_CORES))
    topo = by.get(f"engine_cluster2_{ntopo}c")
    flat = by.get(f"engine_{ntopo}c")
    if topo and flat:
        head["cluster2_overhead_vs_flat"] = (
            topo["wall_s"] / flat["wall_s"] - 1.0)
    grid = by["grid256"]
    head["grid256_points_per_s"] = grid["points_per_s"]
    if "engine_1024c" in by:                    # full (non-QUICK) pass
        head["grid256_speedup_vs_pre_pr"] = (
            grid["points_per_s"] / PRE_PR["grid256_points_per_s"])
    for u in UNROLLS:
        head[f"unroll{u}_Mcyc_per_s"] = (
            by[f"unroll_{u}"]["core_cycles_per_s"] / 1e6)
    # telemetry acceptance: w=64 overhead at the largest measured core
    # count, on both backends (bar: <= 0.10)
    ntop = max(TELE_CORES)
    for tag, label in (("tele", "scan"), ("tele_interp", "interp")):
        r = by.get(f"{tag}_w64_{ntop}c")
        if r:
            head[f"tele_w64_overhead_{label}_{ntop}c"] = r["overhead_vs_w0"]
    return head
