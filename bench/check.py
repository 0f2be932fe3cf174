"""What decides ``correct``: the numbers compared and their limits.

Every number is a count of disagreements, and every limit is 0: the
simulator is deterministic integer arithmetic, so a sound run agrees
with the plain reference (``reference.py``) in every statistic, and a
run either repeats itself exactly or is at fault.

* ``fields_off`` — (point, statistic) pairs of the window's first pass
  that differ from the reference: every counter and final state array
  the engine returns, the latency histogram, the bank-side protocol
  state and the derived metric set;
* ``repeat_off`` — (pass, point) pairs of the window (and the traced
  segment) that differ from the window's first pass in any statistic;
* ``not_ok`` — points returned as sweep error records;
* ``check_failed`` — points whose workload invariants
  (``Result.check()``) fail;
* ``missing`` — points a pass did not return exactly once.
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

LIMITS = {"fields_off": 0, "repeat_off": 0, "not_ok": 0,
          "check_failed": 0, "missing": 0}


def fields_differ(a: Mapping[str, Any], b: Mapping[str, Any]) -> List[str]:
    """Statistics whose values are not identical in ``a`` and ``b`` (a key
    present in one only counts as differing)."""
    bad = sorted(set(a) ^ set(b))
    for k in sorted(set(a) & set(b)):
        x, y = np.asarray(a[k]), np.asarray(b[k])
        nan = x.dtype.kind == "f" and y.dtype.kind == "f"
        if x.shape != y.shape or not np.array_equal(x, y, equal_nan=nan):
            bad.append(k)
    return bad


def judge(passes: Sequence[Sequence[Any]], reference: Sequence[Mapping],
          dups: int = 0) -> Dict[str, Any]:
    """Compare the passes of a run.  ``passes`` holds, per pass, the
    Results in point order (None where a point never came);
    ``reference`` the reference's statistics for the same points;
    ``dups`` the points that came more than once.
    Returns the numbers with their limits, ``failed`` (the points of
    all passes that are not correct), the first disagreements found,
    and ``correct``."""
    first = passes[0]
    off, notes = 0, []
    bad_points = set()
    for i, (got, ref) in enumerate(zip(first, reference)):
        if got is None or not got.ok:
            continue
        d = fields_differ(got.stats, ref)
        off += len(d)
        if d:
            bad_points.add(i)
            if len(notes) < 4:
                notes.append(f"point {i}: {d[:6]}")
    repeat, not_ok, check_failed, failed = 0, 0, 0, 0
    missing = dups
    for pi, results in enumerate(passes):
        missing += sum(r is None for r in results)
        for i, r in enumerate(results):
            bad = r is None or i in bad_points
            if r is not None and not r.ok:
                not_ok += 1
                bad = True
            elif r is not None:
                if pi > 0 and first[i] is not None and fields_differ(
                        r.stats, first[i].stats):
                    repeat += 1
                    bad = True
                try:
                    r.check()
                except Exception as e:  # noqa: BLE001 - any failure counts
                    check_failed += 1
                    bad = True
                    if len(notes) < 4:
                        notes.append(f"check: {type(e).__name__}: {e}")
            failed += bad
    numbers = {"fields_off": off, "repeat_off": repeat, "not_ok": not_ok,
               "check_failed": check_failed, "missing": missing}
    return dict(numbers=numbers, failed=failed, notes=notes,
                correct=all(numbers[k] <= LIMITS[k] for k in numbers))
