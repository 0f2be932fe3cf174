"""Device time by engine stage, idle time split in two, and idle gaps
named by the program's own host spans.

The engine runs each stage of a simulated cycle under a
``jax.named_scope`` (``sim.issue``, ``sim.retire``, ``sim.network``,
``sim.arbitrate``, ``sim.wake``, ``sim.account``, ``sim.telemetry``,
``sim.faults``; ``repro.core.sim.simulate``).  A scope survives as
``op_name`` metadata in the compiled program, but a trace's device
events carry only the instruction's text.  So the ops of a trace are
matched by instruction name against the optimized HLO text of the same
executable (``compiled.as_text()``), which :func:`stage_map` turns
into instruction -> stage by these rules, in order:

1. the stage named in the instruction's own ``op_name``;
2. the most common stage among the instructions of the computations
   it calls (a fusion's body);
3. the stage of an operand's producer, then of a user (``jnp.cumsum``
   lowers to reduce-windows whose ``op_name`` is bare);
4. ``unscoped``.

Stage times are read only where at least :data:`MIN_MATCHED` of the
run executable's device time matches an instruction of that text, and
only where the program has scopes at all; otherwise they are None,
never guessed.  The engine-step kernel keeps its own time
(``kernel_s``) and is left out of ``sim.arbitrate``.

Idle time of the traced window is split by the device's ``XLA
Modules`` line: idle inside an interval of the run's executable is
loop and per-op overhead; idle outside it is the host between calls.
The two add up to the window's idle time.  Gaps are named by the
innermost host span around their midpoint, ``bench.*`` and the
program's ``repro.*`` spans (``repro.obs.runreport.span``) alike.

On the chip, for one cell (a single-run cell gets the stage split;
both kinds get the idle split, the named gaps and the host span
totals), with the cost of tracing measured as the rate with the
profiler running against the rate without it::

    python bench/stages.py --workload terapool1024.rmw_hot4 --seed 1

Prints ``key=value`` lines and, last, one JSON object.
"""
from __future__ import annotations

import collections
import importlib.util
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))


def _bench_module(name: str):
    """A module of this directory by file (``trace`` would otherwise be
    Python's own)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tr = _bench_module("trace")

#: the stages the engine's scan body is scoped by, in program order
STAGES = ("issue", "retire", "network", "arbitrate", "wake", "account",
          "telemetry", "faults")
UNSCOPED = "unscoped"
#: least share of the run executable's device time that has to match an
#: instruction of the HLO text before stage times are read
MIN_MATCHED = 0.99
#: the executables whose intervals bound the in-loop idle time
RUN_MODULE, STUDY_MODULE = "jit__run", "jit__sweep_group"
#: the line of a device plane whose events are the executables run
MODULE_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "repro.")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(")
_INST = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)sim\.([a-z]+)(?=/|$)")

Inst = collections.namedtuple("Inst", "name opcode comp refs scope text")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``sim.<stage>`` of an ``op_name`` path (a stage of
    :data:`STAGES`), or None."""
    found = [s for s in _SCOPE.findall(op_name) if s in STAGES]
    return f"sim.{found[-1]}" if found else None


def parse_hlo(text: str) -> Tuple[Dict[str, Inst], Dict[str, List[str]]]:
    """Instructions of an HLO module's text, by name, and the names of
    each computation's instructions in order."""
    lines = text.splitlines()
    comps = {m.group(1) for ln in lines if (m := _HEADER.match(ln))}
    insts: Dict[str, Inst] = {}
    body: Dict[str, List[str]] = {}
    comp = None
    for ln in lines:
        m = _HEADER.match(ln)
        if m:
            comp = m.group(1)
            body[comp] = []
            continue
        m = _INST.match(ln)
        if not m or comp is None:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        on = _OP_NAME.search(rest)
        insts[name] = Inst(name, op.group(1) if op else "", comp,
                           tuple(_REF.findall(rest.split(" metadata=")[0])),
                           scope_of(on.group(1)) if on else None, rest)
        body[comp].append(name)
    # a reference is a called computation or an operand: keep both kinds
    # apart once every name is known
    return ({n: i._replace(refs=tuple(r for r in i.refs
                                      if r in comps or r in insts))
             for n, i in insts.items()}, body)


def stage_map(text: str) -> Dict[str, str]:
    """Instruction name -> ``sim.<stage>`` or ``unscoped`` for every
    instruction of an optimized HLO module's text (rules in the module
    docstring)."""
    insts, body = parse_hlo(text)
    memo: Dict[str, collections.Counter] = {}

    def comp_scopes(c: str) -> collections.Counter:
        if c not in memo:
            memo[c] = collections.Counter()
            for n in body.get(c, ()):
                i = insts[n]
                if i.scope:
                    memo[c][i.scope] += 1
                for r in i.refs:
                    if r in body:
                        memo[c].update(comp_scopes(r))
        return memo[c]

    stage: Dict[str, Optional[str]] = {}
    for n, i in insts.items():
        s = i.scope
        if s is None:
            called = collections.Counter()
            for r in i.refs:
                if r in body:
                    called.update(comp_scopes(r))
            if called:
                s = called.most_common(1)[0][0]
        stage[n] = s
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for n, i in insts.items():
        for r in i.refs:
            if r in insts:
                users[r].append(n)
    changed = True
    while changed:
        changed = False
        for n, i in insts.items():
            if stage[n] is not None:
                continue
            near = [r for r in i.refs if r in insts] + users[n]
            s = next((stage[r] for r in near if stage[r] is not None), None)
            if s is not None:
                stage[n] = s
                changed = True
    return {n: s or UNSCOPED for n, s in stage.items()}


def loop_body(text: str) -> List[Inst]:
    """The instructions of the scan's ``while`` body computation (the
    body of the first ``while`` in the module)."""
    insts, body = parse_hlo(text)
    for i in insts.values():
        if i.opcode == "while":
            comp = re.search(r"body=%?([\w.\-]+)", i.text).group(1)
            return [insts[n] for n in body[comp]]
    raise ValueError("no while loop in the HLO text")


# ---- the trace ------------------------------------------------------------
def load(path: str) -> Dict:
    """Events of one trace file, in seconds: per device plane its leaf
    ops ``(start, end, name, kernel?)`` and its executables ``(start,
    end, module)``, and the host's ``bench.*`` and ``repro.*`` spans
    ``(start, end, name)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    spans = []
    names: Dict[str, tuple] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in (tr.OP_LINE, MODULE_LINE):
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if line.name == MODULE_LINE:
                        mods.append((s, e, ev.name.split("(")[0]))
                        continue
                    nm = names.get(ev.name)
                    if nm is None:
                        nm = names[ev.name] = (tr.short_name(ev.name),
                                               tr.is_kernel(ev.name))
                    ops.append((s, e) + nm)
            if ops:
                devices[plane.name] = tr.leaves(ops)
                modules[plane.name] = sorted(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return {"devices": devices, "modules": modules, "spans": spans}


def marks(raw: Dict) -> Tuple[float, float]:
    """The traced window: the harness's start and stop marks."""
    m = {n: s for s, e, n in raw["spans"]
         if n in (tr.START_MARK, tr.STOP_MARK)}
    return m[tr.START_MARK], m[tr.STOP_MARK]


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two interval sets."""
    a, b = tr.merge(a), tr.merge(b)
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _module_spans(raw: Dict, dev: str, module: str, lo: float, hi: float):
    return tr.clip([(s, e) for s, e, m in raw["modules"].get(dev, ())
                    if m == module], lo, hi)


def idle_split(raw: Dict, window: Tuple[float, float], module: str
               ) -> Dict[str, float]:
    """Idle seconds of the window, mean over devices: ``in_module_s``
    inside the intervals of ``module``'s executables (gaps between two
    of its ops), ``outside_s`` the rest; they sum to ``idle_s``."""
    lo, hi = window
    acc = collections.Counter()
    for dev, ops in raw["devices"].items():
        free = tr.gaps([(s, e) for s, e, _, _ in ops], lo, hi)
        idle = sum(e - s for s, e in free)
        inside = overlap(free, _module_spans(raw, dev, module, lo, hi))
        acc["idle_s"] += idle
        acc["in_module_s"] += inside
        acc["outside_s"] += idle - inside
    n = max(len(raw["devices"]), 1)
    return {k: acc[k] / n for k in ("idle_s", "in_module_s", "outside_s")}


def stage_times(raw: Dict, smap: Dict[str, str],
                window: Tuple[float, float], module: str = RUN_MODULE
                ) -> Dict:
    """Device seconds of ``module``'s ops in the window by stage, mean
    over devices.  ``stages`` maps every stage of :data:`STAGES` and
    ``unscoped`` to seconds, the kernel left out (``kernel_s``); it is
    None where under :data:`MIN_MATCHED` of the time matched an
    instruction of ``smap`` or no instruction carries a scope.
    ``top_ops`` lists the ten ops with the most time as ``[name, stage,
    seconds]``."""
    lo, hi = window
    per = collections.Counter()
    by_op = collections.Counter()
    total = matched = kernel = 0.0
    for dev, ops in raw["devices"].items():
        mods = tr.merge(_module_spans(raw, dev, module, lo, hi))
        for s, e, nm, kern in ops:
            s, e = max(s, lo), min(e, hi)
            mid = 0.5 * (s + e)
            if e <= s or not any(a <= mid < b for a, b in mods):
                continue
            total += e - s
            by_op[nm] += e - s
            if kern:
                kernel += e - s
            st = smap.get(nm)
            if st is None:
                continue
            matched += e - s
            if not kern:
                per[st] += e - s
    n = max(len(raw["devices"]), 1)
    share = matched / total if total else 0.0
    scoped = any(s != UNSCOPED for s in smap.values())
    stages = None
    if scoped and share >= MIN_MATCHED:
        stages = {k: per[k] / n for k in
                  [f"sim.{s}" for s in STAGES] + [UNSCOPED]}
    return dict(stages=stages, matched_share=share, busy_s=total / n,
                kernel_s=kernel / n,
                top_ops=[[nm, smap.get(nm), s / n]
                         for nm, s in by_op.most_common(10)])


def named_gaps(raw: Dict, window: Tuple[float, float], top: int = 10
               ) -> List[list]:
    """The ``top`` longest idle gaps of the window on any device, each
    named by the innermost ``bench.*`` or ``repro.*`` span around its
    midpoint."""
    lo, hi = window
    found = []
    for ops in raw["devices"].values():
        found += [(e - s, s, e) for s, e in
                  tr.gaps([(s, e) for s, e, _, _ in ops], lo, hi)]
    return [[tr.innermost(raw["spans"], 0.5 * (s + e)), g]
            for g, s, e in sorted(found, reverse=True)[:top]]


# ---- on the chip ------------------------------------------------------------
def measure(c: Dict, seed: int, seconds: float, require=None) -> Dict:
    """Measure cell ``c`` (as ``cells.cell`` returns it): warm it, take
    one traced segment as the harness takes it, then the cost of
    tracing (windows of ``seconds`` off, on, off, on), each window
    under a ``RunReport`` of its own.  ``require`` checks the chips
    (default: the harness's check)."""
    import shutil
    import sys
    import time

    import run as harness

    import jax
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    (require or harness.require_devices)(jax, c["chips"])
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import repro.sync as sync
    from repro import obs
    from repro.core import sim

    mode = c["traffic"]["mode"]
    specs = [sync.Spec(**f) for f in harness.cells.point_fields(c, seed)]
    annotate = jax.profiler.TraceAnnotation
    drv = harness.Entry(sync, specs, mode, annotate)
    drv.one()                                            # warm every shape
    work = (sum(s.topology.n_cores * s.costs.cycles for s in specs)
            if mode == "single" else len(specs))
    out = {"cell": c["name"], "seed": seed,
           "unit": "core_cycles_per_s" if mode == "single"
           else "points_per_s"}
    tbase = os.path.join(harness.ROOT, ".bench_trace")

    # ---- the traced segment, as bench/run.py takes it -------------------
    tdir = os.path.join(tbase, "stages")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    n0, stopped = len(drv.landed), []

    def stop():
        with annotate(tr.STOP_MARK):
            pass
        jax.profiler.stop_trace()
        stopped.append(len(drv.landed))

    drv.report = obs.RunReport()
    with obs.collect(drv.report):
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with annotate(tr.START_MARK):
            pass
        deadline = time.perf_counter() + harness.TRACE_SECONDS
        while not stopped:
            drv.one(deadline, stop)
    raw = load(tr.find_xplane(tdir))
    shutil.rmtree(tdir, ignore_errors=True)
    win = marks(raw)
    win_s = win[1] - win[0]
    split = idle_split(raw, win,
                       RUN_MODULE if mode == "single" else STUDY_MODULE)
    out.update(window_s=win_s,
               idle_pct=100.0 * split["idle_s"] / win_s,
               in_module_idle_pct=100.0 * split["in_module_s"] / win_s,
               outside_idle_pct=100.0 * split["outside_s"] / win_s,
               idle_gaps=named_gaps(raw, win))
    if mode == "single":
        cycles = sum(specs[i].costs.cycles
                     for i in drv.landed[n0:stopped[0]])
        text = sim._run.lower(specs[0].to_params()).compile().as_text()
        st = stage_times(raw, stage_map(text), win)
        per = 1e6 / cycles
        out.update(
            traced_cycles=cycles, matched_share=st["matched_share"],
            busy_us_per_cycle=st["busy_s"] * per,
            kernel_us_per_cycle=st["kernel_s"] * per,
            scan_stages_us_per_cycle=(st["busy_s"] - st["kernel_s"]) * per,
            stage_us_per_cycle=(None if st["stages"] is None else
                                {k: v * per
                                 for k, v in st["stages"].items()}),
            scan_gap_us_per_cycle=split["in_module_s"] * per,
            top_ops=[[nm, s, v * per] for nm, s, v in st["top_ops"]])

    # ---- the cost of tracing, and the host spans of untraced windows ----
    def window(traced: bool):
        drv.report = rep = obs.RunReport()
        tdir = os.path.join(tbase, "cost")
        if traced:
            jax.profiler.start_trace(tdir)
        with obs.collect(rep):
            n, t0 = 0, time.perf_counter()
            while True:
                drv.one()
                n += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            took = time.perf_counter() - t0
        if traced:
            jax.profiler.stop_trace()
            shutil.rmtree(tdir, ignore_errors=True)
        return n * work / took, {k: 100.0 * v[0] / took
                                 for k, v in rep.spans.items()}

    runs = [(traced, window(traced)) for traced in (False, True) * 2]
    out["rate_off"] = [r for traced, (r, _) in runs if not traced]
    out["rate_on"] = [r for traced, (r, _) in runs if traced]
    out["tracing_cost_pct"] = 100.0 * (1.0 - sum(out["rate_on"])
                                       / sum(out["rate_off"]))
    out["span_pct_off"] = [pct for traced, (_, pct) in runs if not traced]
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    import run as harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="each window of the tracing-cost comparison")
    args = ap.parse_args(argv)
    try:
        out = measure(harness.cells.cell(args.workload), args.seed,
                      args.seconds)
    except harness.NoDevice as e:
        print(f"stages: {e}", file=sys.stderr)
        return 2
    for k, v in out.items():
        print(f"{k}={v!r}", flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
