"""The reduction from a trace to numbers, on a trace small enough to work
by hand and on the heads of traces recorded on the chip; and the
benchmark's own counts of operations and bytes against hand-worked
values."""

import os

import pytest

from harness import device, manifest, trace_reduce
from harness.trace_reduce import Trace

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _hand_trace():
    """One device, times in ns:

        op.1          [   0,  100)
        op.2          [ 100,  250)       idle [250, 400)
        all-reduce.1  [ 400,  500)
        fusion.3      [ 450,  600)       idle [600, 1000)
        flash         [1000, 1300)

    busy 250 + 200 + 300 = 750 of a 1300 window; gaps 150 and 400; the
    all-reduce runs alone for [400, 450) = 50; the kernel for 300."""
    return Trace(
        devices={"/device:TPU:0": [
            ("op.1", 0, 100), ("op.2", 100, 150), ("all-reduce.1", 400, 100),
            ("fusion.3", 450, 150), ("flash", 1000, 300)]},
        host=[("dispatch", 240, 140), ("wait_loss", 590, 400),
              ("stamp", 995, 3)],
        opcodes={"op.1": "fusion", "op.2": "fusion",
                 "all-reduce.1": "all-reduce", "fusion.3": "fusion",
                 "flash": "custom-call"},
        kernels={"flash"})


def _readers(names):
    return {n: manifest.load_module("layer_metrics", n) for n in names}


def test_hand_worked_trace():
    trace = _hand_trace()
    events = trace.devices["/device:TPU:0"]
    assert trace_reduce.busy_ns(events) == 750
    assert trace_reduce.mean_busy_ns(trace) == 750
    assert trace_reduce.gaps(events) == [(250, 400), (600, 1000)]
    names = trace_reduce.collective_names(trace)
    assert names == {"all-reduce.1"}
    assert trace_reduce.exposed_ns(events, names.__contains__) == 50
    assert trace_reduce.top_operations(trace, 2) == [
        ["flash", 300e-9], ["op.2", 150e-9]]
    assert trace_reduce.longest_gaps(trace, 2) == [
        ["wait_loss", 400e-9], ["dispatch", 150e-9]]


def test_hand_worked_trace_through_the_readers():
    trace = _hand_trace()
    run = {
        "trace": trace, "steps": 2, "chips": 1,
        "window": {"start": 0.0, "end": 1500e-9, "dispatch": [2e-3, 4e-3]},
        "compiled_text": 'custom_call_target="tpu_custom_call"',
        "stamp": {"kind": "TPU v5 lite"}, "memory_peak_bytes": 9_000_000_000,
        "spans": {"init": 7.5, "compile": 6.0},
    }
    r = _readers(["model_device_ms", "device_idle_pct",
                  "device_longest_gap_ms", "kernels_mosaic_ms",
                  "collective_exposed_ms", "dispatch_ms",
                  "device_peak_hbm_gb", "init_s", "compile_s"])
    assert r["model_device_ms"].read(run) == pytest.approx(750 / 2 / 1e6)
    assert r["device_idle_pct"].read(run) == pytest.approx(50.0)
    assert r["device_longest_gap_ms"].read(run) == pytest.approx(400 / 1e6)
    assert r["kernels_mosaic_ms"].read(run) == pytest.approx(300 / 2 / 1e6)
    assert r["collective_exposed_ms"].read(run) == pytest.approx(50 / 2 / 1e6)
    assert r["dispatch_ms"].read(run) == pytest.approx(3.0)
    assert r["device_peak_hbm_gb"].read(run) == pytest.approx(9.0)
    assert r["init_s"].read(run) == 7.5 and r["compile_s"].read(run) == 6.0


def test_readers_with_nothing_to_read_return_nothing():
    run = {"trace": None, "steps": 1, "chips": 1, "compiled_text": "",
           "window": {"start": 0.0, "end": 1.0, "dispatch": []},
           "memory_peak_bytes": 0, "spans": {}}
    for name in ("model_device_ms", "model_device_mfu", "device_idle_pct",
                 "device_longest_gap_ms", "kernels_mosaic_ms",
                 "flash_roofline", "collective_exposed_ms", "dispatch_ms",
                 "device_peak_hbm_gb", "init_s", "compile_s"):
        assert manifest.load_module("layer_metrics", name).read(run) is None
    # No collective and no kernel in a trace that has operations.
    trace = _hand_trace()
    trace.opcodes["all-reduce.1"] = "fusion"
    run.update(trace=trace)
    for name in ("collective_exposed_ms", "kernels_mosaic_ms",
                 "flash_roofline"):
        assert manifest.load_module("layer_metrics", name).read(run) is None


def _sweep_busy(events):
    """Busy time by a second, plainer method: walk the sorted events and
    add what each adds beyond the furthest end seen."""
    busy, reach = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if reach is None or start >= reach:
            busy += dur
            reach = end if reach is None else max(reach, end)
        elif end > reach:
            busy += end - reach
            reach = end
    return busy


RECORDED = {
    # head of a --trace 1 run of each cell on a TPU v5 lite (PR 23):
    # events, busy ns, first-to-last ns, longest gap ns, Mosaic ns
    "resnet50-dp1.trace-head.json.gz":
        (7300, 201_362_918, 201_401_174, 14_563, 0),
    # 54 kernel events of 36 kernels: 2.78, 3.77 and 4.57 ms a call
    "bert-base-s512-dp1.trace-head.json.gz":
        (8000, 470_987_844, 471_028_794, 28_734, 200_751_880),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace(name):
    trace = trace_reduce.load(os.path.join(FIXTURES, name))
    count, busy, window, gap, mosaic = RECORDED[name]
    events = trace.devices["/device:TPU:0"]
    assert len(events) == count
    assert trace_reduce.busy_ns(events) == busy == _sweep_busy(events)
    assert (max(s + d for _, s, d in events) - events[0][1]) == window
    gaps = trace_reduce.gaps(events)
    assert max(b - a for a, b in gaps) == gap
    assert busy + sum(b - a for a, b in gaps) == window
    assert sum(d for n, _, d in events if n in trace.kernels) == mosaic
    assert {n for n, _, _ in trace.host} <= {"dispatch", "wait_loss",
                                             "stamp"}
    assert trace_reduce.longest_gaps(trace, 1)[0][1] == gap / 1e9


def test_recorded_four_chip_trace():
    """The head of ``resnet50-dp4``'s traced run: four device planes, each
    with the step's one combined all-reduce, which runs alone."""
    trace = trace_reduce.load(os.path.join(
        FIXTURES, "resnet50-dp4.trace-head.json.gz"))
    assert sorted(trace.devices) == [f"/device:TPU:{i}" for i in range(4)]
    assert trace_reduce.collective_names(trace) == {"all-reduce"}
    busy = [trace_reduce.busy_ns(trace.devices[d])
            for d in sorted(trace.devices)]
    assert busy == [133_728_753, 133_720_508, 133_721_932, 133_724_571]
    assert trace_reduce.mean_busy_ns(trace) == sum(busy) / 4
    exposed = [trace_reduce.exposed_ns(trace.devices[d],
                                       {"all-reduce"}.__contains__)
               for d in sorted(trace.devices)]
    assert exposed == [1_783_391, 1_783_716, 1_780_784, 1_775_296]
    reader = manifest.load_module("layer_metrics", "collective_exposed_ms")
    assert reader.read({"trace": trace, "steps": 1}) == pytest.approx(
        1.783716)


def test_parse_operation():
    text = ('%fusion.14 = (f32[256]{0:T(256)S(1)}, bf16[256,56,56,256]'
            '{3,0,2,1:T(8,128)(2,1)}) fusion(f32[256]{0} %copy-done.343), '
            'kind=kOutput, calls=%fused_computation.48')
    assert trace_reduce.parse_operation(text) == ("fusion.14", "fusion",
                                                  False)
    text = ('%all-reduce-start.1 = (f32[64]{0}, f32[64]{0}) '
            'all-reduce-start(f32[64]{0} %x), replica_groups={{0,1,2,3}}')
    assert trace_reduce.parse_operation(text)[:2] == (
        "all-reduce-start.1", "all-reduce-start")
    text = ('%custom-call.7 = bf16[64,512,12,64]{3,2,1,0} custom-call('
            'bf16[64,512,12,64]{3,2,1,0} %q), '
            'custom_call_target="tpu_custom_call"')
    assert trace_reduce.parse_operation(text) == ("custom-call.7",
                                                  "custom-call", True)


def test_resnet50_flops_per_image():
    b = manifest.load_module("builders", "resnet_sgd")
    # By hand: 8x8x3 image, 2 filters, one block, 5 classes.
    #   7x7/2 conv to 4x4:        4*4*49*3*2 = 4704 MACs; pool to 2x2
    #   block at 2x2, mid 2:      1x1 16 + 3x3 144 + 1x1 64 + proj 64 = 288
    #   head 8 -> 5:              40
    tiny = {"image_size": 8, "channels": 3, "num_filters": 2,
            "stage_sizes": [1], "num_classes": 5}
    assert b.conv_flops_per_image(tiny) == 2 * (4704 + 288 + 40)
    assert b.train_flops_per_image(tiny) == 3 * 2 * 5032
    # ResNet-50 v1.5 at 224: the published 4.09 GMACs forward.
    full = manifest.load_json(manifest.BENCH_DIR, "configs",
                              "resnet50.json")["model"]
    assert b.conv_flops_per_image(full) / 2 == pytest.approx(4.09e9,
                                                             rel=0.005)
    assert b.train_flops_per_image(full) == pytest.approx(24.5e9, rel=0.01)


def test_bert_flops_per_step():
    b = manifest.load_module("builders", "bert_mlm_adamw")
    model = manifest.load_json(manifest.BENCH_DIR, "configs",
                               "bert-base.json")["model"]
    # matmul parameters: 12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 30522
    #                  = 84,934,656 + 23,440,896 = 108,375,552
    # dense 6 x 32768 tokens x that; attention 12 x 12 x 64 x 512^2 x 768
    dense = 6 * 32768 * 108_375_552
    attention = 12 * 12 * 64 * 512 * 512 * 768
    assert b.train_flops_per_step(model, 64, 512) == dense + attention
    assert dense + attention == pytest.approx(23.2e12, rel=0.005)


def test_flash_work_per_step():
    f = manifest.load_module("layer_metrics", "flash_roofline")
    # One layer, B=64 H=12 S=512 D=64: forward 4BHS^2D = 51,539,607,552,
    # dq 6x, dkdv 8x that quarter: 18 x 12,884,901,888 in all.
    flops, nbytes = f.flash_work(64, 12, 512, 64, 1)
    assert flops == 18 * 64 * 12 * 512 * 512 * 64 == 231_928_233_984
    tile = 64 * 12 * 512 * 64 * 2       # 50,331,648 bytes of bf16
    stat = 64 * 12 * 512 * 4            # 1,572,864 bytes of f32
    assert nbytes == (4 + 5 + 6) * tile + 5 * stat == 762_839_040
    assert f.flash_work(64, 12, 512, 64, 12) == (12 * flops, 12 * nbytes)


def test_peaks_table():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        device.peaks("TPU v9 imaginary")
