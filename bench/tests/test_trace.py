"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e."""
import glob
import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench_trace", os.path.join(BENCH, "trace.py"))
tr = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tr)


def test_merge_overlapping_and_nested():
    assert tr.merge([(3, 5), (0, 2), (1, 3), (6, 7), (6.5, 6.8)]) == [
        (0, 5), (6, 7)]
    assert tr.length([(0, 2), (1, 3), (10, 11), (10.5, 10.6)]) == 4


def test_gaps_inside_window():
    busy = [(1, 2), (1.5, 3), (5, 6), (9, 12)]
    assert tr.gaps(busy, 0, 10) == [(0, 1), (3, 5), (6, 9)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def _raw(lo=0.5, hi=9.5):
    # two devices; kernel ops overlap other ops on device 0
    ops0 = [(1.0, 2.0, "fusion.1", False), (1.5, 2.5, "custom-call.3", True),
            (4.0, 5.0, "custom-call.3", True), (8.0, 9.0, "copy.2", False)]
    ops1 = [(0.0, 1.0, "fusion.1", False), (2.0, 6.0, "fusion.1", False)]
    spans = [(lo, lo, tr.START_MARK), (0.6, 3.0, "bench.study_next"),
             (5.5, 9.0, "bench.study_next"), (6.0, 7.9, "bench.inner"),
             (hi, hi, tr.STOP_MARK)]
    return {"devices": {"/device:TPU:0": ops0, "/device:TPU:1": ops1},
            "spans": spans}


def test_reduce_busy_kernel_and_gaps():
    r = tr.reduce(_raw())
    assert r["window_s"] == pytest.approx(9.0)
    d0, d1 = r["devices"]["/device:TPU:0"], r["devices"]["/device:TPU:1"]
    # device 0: union (1, 2.5) + (4, 5) + (8, 9) = 3.5, kernel 1 + 1
    assert d0["busy_s"] == pytest.approx(3.5)
    assert d0["kernel_s"] == pytest.approx(2.0)
    assert d0["other_s"] == pytest.approx(1.5)
    # device 1 clipped to the window: (0.5, 1) + (2, 6) = 4.5
    assert d1["busy_s"] == pytest.approx(4.5)
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["n_devices"] == 2
    gaps = r["breakdown"]["idle_gaps"]
    # the longest gap is device 1's (6, 9.5), mid 7.75 inside bench.inner
    assert gaps[0] == ["bench.inner", pytest.approx(3.5)]
    assert len(gaps) <= 10
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((1.0 + 0.5 + 4.0) / 2)


def test_kernel_marks_and_names():
    k = ('%closed_call.16 = (s32[1,1024]) custom-call(s32[1,3] %a), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints')
    assert tr.is_kernel(k)
    assert not tr.is_kernel('%custom-call.10 = s32[256] custom-call(), '
                            'custom_call_target="AllocateBuffer"')
    assert tr.short_name(k) == "closed_call.16"


def test_containers_are_dropped():
    ops = [(0.0, 10.0, "while.7", False), (1.0, 2.0, "fusion.1", False),
           (3.0, 4.0, "closed_call.16", True), (11.0, 12.0, "copy", False)]
    assert [o[2] for o in tr.leaves(ops)] == ["fusion.1", "closed_call.16",
                                             "copy"]


def test_window_from_start_and_stop_marks():
    r = tr.reduce(_raw(2.0, 6.0))
    assert r["window_s"] == pytest.approx(4.0)
    # device 0 in (2, 6): (2, 2.5) + (4, 5)
    assert r["devices"]["/device:TPU:0"]["busy_s"] == pytest.approx(1.5)


RECORDED = glob.glob(os.path.join(BENCH, "testdata", "*", "**",
                                  "*.xplane.pb"), recursive=True)


@pytest.mark.skipif(not RECORDED, reason="no recorded trace")
def test_recorded_chip_trace():
    # one repro.sync.run of the hist1024 machine cut to 200 cycles, on a
    # TPU v5e, inside a bench.run span: one engine_step kernel call per
    # simulated cycle, the while loop's event dropped as a container
    raw = tr.load(RECORDED[0])
    assert list(raw["devices"]) == ["/device:TPU:0"]
    assert [n for _, _, n in raw["spans"]] == ["bench.run"]
    r = tr.reduce(raw, window=raw["spans"][0][:2])
    assert r["kernel_calls"] == 200
    assert not any(n.startswith("while") for n, _ in
                   r["breakdown"]["device_ops"])
    assert r["breakdown"]["device_ops"][0][0].startswith("closed_call")
    # about 89 us of operations per cycle, the kernel 37 of them
    assert 0.0175 < r["busy_s"] < 0.0181
    assert 0.0072 < r["kernel_s"] < 0.0075
    assert r["busy_s"] < r["window_s"]
