"""The trace reduction: device busy time as a union of op intervals, per-op
device time, idle gaps labelled by the host span, and kernel names read
from a compiled module's Mosaic custom calls."""
import base64

import pytest

from bench import trace

MS = 1_000_000  # ns


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    ops = {"/device:TPU:0": [
        ("fusion.1", 0 * MS, 10 * MS),      # starts before the window
        ("fusion.2", 15 * MS, 30 * MS),
        ("convolution.7", 20 * MS, 40 * MS),  # overlaps fusion.2
        ("fusion.3", 60 * MS, 70 * MS),
        ("copy.4", 95 * MS, 120 * MS),      # ends after the window
    ]}
    spans = [("bench.window", 5 * MS, 100 * MS),
             ("bench.wait", 40 * MS, 58 * MS),
             ("bench.dispatch", 58 * MS, 61 * MS),
             ("bench.take", 72 * MS, 94 * MS)]
    s = trace.reduce_events(ops, spans)
    assert s["window_s"] == pytest.approx(0.095)
    # busy: [5,10] + [15,40] + [60,70] + [95,100] = 5 + 25 + 10 + 5 ms
    assert s["busy_s"] == pytest.approx(0.045)
    assert s["ops"]["fusion"] == pytest.approx(0.005 + 0.015 + 0.010)
    assert s["ops"]["convolution"] == pytest.approx(0.020)
    assert s["ops"]["copy"] == pytest.approx(0.005)
    # gaps: [10,15] no span, [40,60] wait 18 of 20, [70,95] take 22 of 25
    assert s["idle_by_span"]["host.other"] == pytest.approx(0.005)
    assert s["idle_by_span"]["bench.wait"] == pytest.approx(0.020)
    assert s["idle_by_span"]["bench.take"] == pytest.approx(0.025)
    assert s["window_s"] - s["busy_s"] == pytest.approx(
        sum(s["idle_by_span"].values()))


def test_two_devices_average_their_busy_time():
    ops = {"/device:TPU:0": [("fusion.1", 0, 50 * MS)],
           "/device:TPU:1": [("fusion.1", 0, 100 * MS)]}
    s = trace.reduce_events(ops, [("bench.window", 0, 100 * MS)])
    assert s["busy_s"] == pytest.approx(0.075)
    assert s["ops"]["fusion"] == pytest.approx(0.075)


def test_no_window_or_no_device_reads_nothing():
    assert trace.reduce_events({}, [("bench.window", 0, 1)]) == {}
    assert trace.reduce_events({"/device:TPU:0": [("a", 0, 1)]}, []) == {}


def test_kernel_names_come_from_the_mosaic_body():
    body = base64.b64encode(b"MLIR\x00stable_mosaic\x00_mask_kernel\x00repro")
    hlo = ('  %vmap__.1 = bf16[2,1024,128]{2,1,0} custom-call(%a, %b), '
           'custom_call_target="tpu_custom_call", backend_config={"flag_'
           'configs":[],"custom_call_config":{"body":"' + body.decode()
           + '","needs_layout_passes":true}}\n'
           '  %fusion.3 = f32[8]{0} fusion(%c), kind=kLoop\n')
    names = trace.kernel_names(hlo)
    assert names == {"vmap__.1": "_mask_kernel"}
    s = trace.reduce_events({"/device:TPU:0": [("vmap__.1", 0, 2 * MS),
                                               ("fusion.3", 2 * MS, 3 * MS)]},
                            [("bench.window", 0, 4 * MS)], names)
    assert s["ops"]["_mask_kernel"] == pytest.approx(0.002)


def test_breakdown_keeps_the_ten_largest():
    s = {"ops": {f"op{i}": float(i) for i in range(15)},
         "idle_by_span": {"bench.wait": 2.0, "bench.take": 1.0}}
    b = trace.breakdown(s)
    assert [k for k, _ in b["device_ops"]] == [f"op{i}" for i in
                                               range(14, 4, -1)]
    assert b["idle_gaps"] == [["bench.wait", 2.0], ["bench.take", 1.0]]
