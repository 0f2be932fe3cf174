"""Readings that set the limits of ``check.py``, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3

For each seed, drives the cell's timed entry as a run does (one set-up
pass, then one pass as the window makes it) and compares that pass with

* the plain reference (``reference.py``): the lower reading, what a
  sound run gives;
* the control: the same reference with one stated guarantee broken,
  the one the cell's traffic file names under ``control`` (see
  ``reference.py``), which a sound run must fail.

Prints one line per seed and a JSON summary as its last line.  Needs
the chips the cell asks for, as a run does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import check  # noqa: E402
import run  # noqa: E402


def readings(c, seeds, require=run.require_devices):
    import jax
    jax.config.update("jax_compilation_cache_dir", run.cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    require(jax, c["chips"])
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import repro.sync as sync
    mode = c["traffic"]["mode"]
    rows = []
    for seed in seeds:
        fields = cells.point_fields(c, seed)
        specs = [sync.Spec(**f) for f in fields]
        drv = run.Entry(sync, specs, mode, jax.profiler.TraceAnnotation)
        drv.one()
        t = time.perf_counter()
        got = drv.one()
        pass_s = time.perf_counter() - t
        row = {"seed": seed, "pass_s": pass_s}
        for label, broken in (("reference", None),
                              ("control", c["traffic"]["control"])):
            ref = run.reference_stats(c, fields, broken)
            v = check.judge([got], ref, dups=drv.dups)
            row[label] = v["numbers"]["fields_off"]
            row[label + "_correct"] = v["correct"]
        run.log(f"seed {seed}: pass_s={pass_s:.3f} "
                f"fields_off reference={row['reference']} "
                f"control={row['control']} "
                f"(correct {row['reference_correct']} / "
                f"{row['control_correct']})")
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated --seed values")
    args = ap.parse_args(argv)
    c = cells.cell(args.workload)
    try:
        rows = readings(c, [int(s) for s in args.seeds.split(",")])
    except run.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    print(json.dumps({
        "cell": c["name"],
        "lower": max(r["reference"] for r in rows),
        "upper": min(r["control"] for r in rows), "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
