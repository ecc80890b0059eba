"""The trace reduction: busy and idle over a window, named-operation time,
exposed collective time, idle gaps named by host spans, and the table of
peaks."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import run as bench_run
from bench import trace as T

MS = 1_000_000  # ns


def _trace():
    """Two devices over a window [0, 100) ms.

    dev0: fusion.1 [10, 30), all-gather.2 [25, 45) (15 ms of it exposed
    after fusion.1 ends at 30), fusion.3 [60, 70); an op before the window.
    dev1: fusion.1 [0, 50), reduce-scatter.4 [40, 55) (5 ms exposed).
    Host: window span, a generate span [0, 50), a reference span [50, 100).
    """
    dev0 = [("fusion.0", -20 * MS, -5 * MS),
            ("fusion.1", 10 * MS, 30 * MS),
            ("all-gather.2", 25 * MS, 45 * MS),
            ("fusion.3", 60 * MS, 70 * MS)]
    dev1 = [("fusion.1", 0, 50 * MS),
            ("reduce-scatter.4", 40 * MS, 55 * MS)]
    spans = [("bench.window", 0, 100 * MS),
             ("bench.generate", 0, 50 * MS),
             ("bench.reference", 50 * MS, 100 * MS)]
    return T.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, spans)


def test_busy_and_idle_over_the_window():
    r = T.reduce(_trace())
    # dev0 busy [10,45) + [60,70) = 45 ms; dev1 [0,55) = 55 ms; mean 50
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["idle_s"] == pytest.approx(0.050)
    assert r["devices"] == 2


def test_named_operation_time_is_averaged_over_devices():
    r = T.reduce(_trace())
    assert r["op_s"]["fusion.1"] == pytest.approx((0.020 + 0.050) / 2)
    assert r["op_s"]["all-gather.2"] == pytest.approx(0.020 / 2)
    assert "fusion.0" not in r["op_s"]          # before the window
    top = r["breakdown"]["device_ops"]
    assert top[0][0] == "fusion.1" and len(top) <= T.TOP


def test_exposed_collective_time():
    r = T.reduce(_trace())
    assert r["collective_s"] == pytest.approx((0.020 + 0.015) / 2)
    assert r["exposed_collective_s"] == pytest.approx((0.015 + 0.005) / 2)


def test_idle_gaps_are_named_by_host_spans():
    gaps = T.reduce(_trace())["breakdown"]["idle_gaps"]
    # dev1 idle [55,100) 45 ms, in reference; dev0 [70,100) 30 ms in
    # reference; dev0 [0,10) in generate; dev0 [45,60) midpoint 52.5
    assert gaps[0] == ["bench.reference", pytest.approx(0.045)]
    assert gaps[1] == ["bench.reference", pytest.approx(0.030)]
    names = {g[0] for g in gaps}
    assert names == {"bench.reference", "bench.generate"}
    assert sum(g[1] for g in gaps) == pytest.approx(2 * 0.050)


def test_window_defaults_to_the_window_span():
    tr = _trace()
    assert T.window_of(tr) == (0, 100 * MS)
    tr.spans = [s for s in tr.spans if s[0] != "bench.window"]
    with pytest.raises(ValueError):
        T.window_of(tr)


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        T.reduce(T.Trace({}, [("bench.window", 0, 1)]))


def test_peaks_of_an_unknown_device_kind_raise():
    assert bench_run.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        bench_run.peaks_for("TPU v99")


def test_op_names_are_the_hlo_instruction_names():
    assert T.op_name("%convolution_tanh_fusion = bf16[2048,2048]{1,0} "
                     "fusion(bf16[2048,2048] %a.1), kind=kOutput") == (
        "convolution_tanh_fusion")
    assert T.op_name("jit__lambda(1079)") == "jit__lambda(1079)"


def test_an_asynchronous_collective_counts_where_no_op_runs():
    tr = _trace()
    # dev1: an all-gather in flight [60, 80), with no operation running
    tr.async_ops = {"/device:TPU:1": [("all-gather-start.9", 60 * MS,
                                       80 * MS),
                                      ("copy-start.1", 60 * MS, 90 * MS)]}
    r = T.reduce(tr)
    assert r["collective_s"] == pytest.approx((0.020 + 0.015 + 0.020) / 2)
    assert r["exposed_collective_s"] == pytest.approx(
        (0.015 + 0.005 + 0.020) / 2)
    assert r["busy_s"] == pytest.approx(0.050)   # in flight is not busy


def test_an_operation_holding_others_is_left_out_of_op_time():
    tr = T.Trace({"/device:TPU:0": [("while.5", 0, 10 * MS),
                                    ("fusion.1", 1 * MS, 4 * MS),
                                    ("fusion.2", 4 * MS, 9 * MS)]},
                 [("bench.window", 0, 10 * MS)])
    r = T.reduce(tr)
    assert set(r["op_s"]) == {"fusion.1", "fusion.2"}
    assert r["busy_s"] == pytest.approx(0.010)


RECORDED = Path(__file__).resolve().parent / "data" / \
    "one_chip_matmul.xplane.pb"


def test_a_trace_recorded_on_one_tpu_v5e():
    """Three units on one chip: a jitted bf16 2048^3 matmul, tanh and a
    second matmul each (``bench.unit``), then a 10 ms host sleep
    (``bench.host_wait``). The device's clock runs about 1.4 ms ahead of
    the host's spans in this recording."""
    tr = T.load(str(RECORDED))
    assert list(tr.devices) == ["/device:TPU:0"]
    ops = tr.devices["/device:TPU:0"]
    assert [n for n, _, _ in ops[:4]] == ["copy-start", "copy-done",
                                         "convolution_tanh_fusion", "fusion"]
    assert [n for n, _, _ in tr.spans] == ["bench.unit", "bench.host_wait"] * 3
    lo, hi = tr.spans[0][1] - 5 * MS, tr.spans[-1][2]
    r = T.reduce(tr, (lo, hi))
    busy = sum(e - s for _, s, e in ops) * 1e-9
    assert r["busy_s"] == pytest.approx(busy)
    assert r["op_s"]["convolution_tanh_fusion"] == pytest.approx(
        3 * 90.86e-6, rel=1e-3)
    assert r["collective_s"] == 0
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["breakdown"]["idle_gaps"][0][0] == "bench.host_wait"
    assert r["idle_s"] == pytest.approx(r["window_s"] - busy)
