"""Quickstart: reproduce the paper's headline result (Fig. 3 ratios)
through the public ``repro.sync`` API, then run one concurrent-algorithm
workload from the workload registry through every class of protocol.

    PYTHONPATH=src python examples/quickstart.py

``REPRO_BENCH_QUICK=1`` (the CI smoke) trims the simulated horizons;
the headline ratios then drift from the paper's numbers, the mechanics
don't.
"""
import os

from repro.sync import Spec, Study, run, scenario, workloads

QUICK = bool(int(os.environ.get("REPRO_BENCH_QUICK", "0")))


def main():
    print("=== 1. paper headline: Colibri vs LRSC (Fig. 3) ===")
    study = Study(Spec(cycles=2_000 if QUICK else 20_000)) \
        .grid(protocol=("colibri", "lrsc"), n_addrs=(1, 256))
    t = {(r.spec.protocol.name, r.spec.topology.n_addrs): r.throughput
         for r in study.run()}
    hi = t[("colibri", 1)] / t[("lrsc", 1)]
    lo = t[("colibri", 256)] / t[("lrsc", 256)]
    print(f"high contention: colibri/lrsc = {hi:.2f}x (paper: 6.5x)")
    print(f"low contention:  colibri/lrsc = {lo:.2f}x (paper: 1.13x)\n")

    print("=== 2. workload registry: a concurrent queue, three protocols ===")
    print(f"registered workloads: {', '.join(workloads())}")
    for proto in ("colibri", "lrsc", "amo_lock"):
        r = run(Spec(protocol=proto, workload="ms_queue", n_cores=64,
                     cycles=2_000 if QUICK else 6_000, record_trace=True,
                     **scenario("ms_queue")))
        info = r.check()                         # linearizability screen
        print(f"  {proto:9s} enq+deq pairs/cycle = {r.throughput:.4f}  "
              f"polls = {r.polls:5d}  "
              f"(pushes={info['pushes']}, pops={info['pops']})")


if __name__ == "__main__":
    main()
