"""``harness/scopes.py`` and ``harness/program_log.py`` on a compiled text
and a trace small enough to work by hand, every reader PR 24 added on
them, and the same on heads recorded on the chip (a trace's first events
with the slice of the compiled text that explains them:
``tools/scope_table.py --head``)."""

import gzip
import json
import os

import pytest

from harness import manifest, program_log, scopes, trace_reduce
from harness.trace_reduce import Trace

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")

STEP = "jit(train_step)/shard_map/"
FWD = STEP + "jvp(Net)/layer_0/Dense_0/"
BWD = STEP + "transpose(jvp(Net))/layer_0/Dense_0/"

# A compiled step as XLA prints it, cut to what the parser reads: one
# fusion of each phase, one that recomputes a forward value inside the
# backward pass, one that is mixed, an instruction nobody scoped, an
# instruction with no metadata, and two Mosaic calls of different names.
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_forward (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  %c.1 = f32[] constant(2), metadata={{op_name="{STEP}mul"}}
  ROOT %m.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{FWD}dot_general" stack_frame_id=4}}
}}

%fused_backward (p0: f32[8]) -> f32[8] {{
  %p0.1 = f32[8]{{0}} parameter(0)
  ROOT %m.2 = f32[8]{{0}} multiply(%p0.1, %p0.1), metadata={{op_name="{BWD}dot_general"}}
}}

%fused_recompute (p0: f32[8]) -> f32[8] {{
  %p0.2 = f32[8]{{0}} parameter(0)
  %r.1 = f32[8]{{0}} tanh(%p0.2), metadata={{op_name="{FWD}tanh"}}
  ROOT %m.3 = f32[8]{{0}} multiply(%r.1, %p0.2), metadata={{op_name="{BWD}mul"}}
}}

%fused_update (p0: f32[8]) -> f32[8] {{
  %p0.3 = f32[8]{{0}} parameter(0)
  ROOT %a.1 = f32[8]{{0}} add(%p0.3, %p0.3), metadata={{op_name="{STEP}hvd.update/add"}}
}}

%fused_mixed (p0: f32[8]) -> f32[8] {{
  %p0.4 = f32[8]{{0}} parameter(0)
  %g.1 = f32[8]{{0}} multiply(%p0.4, %p0.4), metadata={{op_name="{BWD}transpose"}}
  ROOT %a.2 = f32[8]{{0}} add(%g.1, %p0.4), metadata={{op_name="{STEP}hvd.update/add"}}
}}

%region_add (x: f32[], y: f32[]) -> f32[] {{
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y), metadata={{op_name="{STEP}hvd.exchange/hvd.allreduce.DistributedOptimizer.0/psum"}}
}}

ENTRY %main.9 (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_forward, metadata={{op_name="{FWD}dot_general"}}
  %hvd_flash_fwd.1 = f32[8]{{0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}jvp(Net)/layer_0/Attn_0/hvd_flash_fwd/pallas_call"}}
  %fusion.2 = f32[8]{{0}} fusion(%hvd_flash_fwd.1), kind=kLoop, calls=%fused_backward
  %hvd_flash_bwd_dq.1 = f32[8]{{0}} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}transpose(jvp(Net))/layer_0/Attn_0/hvd_flash_bwd_dq/pallas_call"}}
  %fusion.3 = f32[8]{{0}} fusion(%hvd_flash_bwd_dq.1), kind=kLoop, calls=%fused_recompute
  %all-reduce.1 = f32[8]{{0}} all-reduce(%fusion.3), replica_groups={{{{0,1,2,3}}}}, to_apply=%region_add, metadata={{op_name="{STEP}hvd.exchange/hvd.allreduce.DistributedOptimizer.0/psum"}}
  %fusion.4 = f32[8]{{0}} fusion(%all-reduce.1), kind=kLoop, calls=%fused_update
  %fusion.5 = f32[8]{{0}} fusion(%fusion.4), kind=kLoop, calls=%fused_mixed
  %add.7 = f32[8]{{0}} add(%fusion.5, %fusion.5), metadata={{op_name="{STEP}add"}}
  ROOT %copy.1 = f32[8]{{0}} copy(%add.7)
}}
'''

EXPECTED_PHASES = {
    "fusion.1": "forward", "hvd_flash_fwd.1": "forward",
    "fusion.2": "backward", "hvd_flash_bwd_dq.1": "backward",
    "fusion.3": "backward",         # forward + backward: recomputation
    "all-reduce.1": "exchange", "fusion.4": "update",
    "fusion.5": "mixed", "add.7": "none",
}


def _hand_trace():
    """One device, one step, times in ns, one after another:

        fusion.1 100, hvd_flash_fwd.1 300, fusion.2 200,
        hvd_flash_bwd_dq.1 500, fusion.3 50, all-reduce.1 40,
        fusion.4 30, fusion.5 20, add.7 10, copy.1 5

    forward 400, backward 750, exchange 40, update 30, mixed 20, none
    10 + 5 (``copy.1`` carries no metadata): 1255 busy."""
    durations = [("fusion.1", 100), ("hvd_flash_fwd.1", 300),
                 ("fusion.2", 200), ("hvd_flash_bwd_dq.1", 500),
                 ("fusion.3", 50), ("all-reduce.1", 40), ("fusion.4", 30),
                 ("fusion.5", 20), ("add.7", 10), ("copy.1", 5)]
    events, at = [], 1000
    for name, d in durations:
        events.append((name, at, d))
        at += d
    return Trace(devices={"/device:TPU:0": events}, host=[],
                 opcodes={n: "fusion" for n, _ in durations},
                 kernels={"hvd_flash_fwd.1", "hvd_flash_bwd_dq.1"})


def _run(**over):
    run = {"trace": _hand_trace(), "steps": 1, "chips": 1,
           "compiled_text": HAND_TEXT,
           "window": {"start": 10.0, "end": 11.0, "dispatch": []}}
    run.update(over)
    return run


def _read(name, run):
    return manifest.load_module("layer_metrics", name).read(run)


# ------------------------------------------------------------- scopes

@pytest.mark.parametrize("op_name,expected", [
    (FWD + "dot_general", "forward"),
    (BWD + "dot_general", "backward"),
    (STEP + "hvd.update/add", "update"),
    (STEP + "hvd.update/jvp(x)/add", "update"),
    (STEP + "hvd.exchange/convert_element_type", "exchange"),
    (STEP + "transpose(jvp(Net))/hvd.allreduce.DistributedGrad.3/psum",
     "exchange"),
    (STEP + "add", "none"),
    ("reduce_sum", "none"),
])
def test_phase_of_an_op_name(op_name, expected):
    assert scopes.phase(op_name) == expected


def test_op_names_follow_a_fusion_into_its_computation():
    names = scopes.op_names(HAND_TEXT)
    assert names["fusion.1"] == {FWD + "dot_general", STEP + "mul"}
    assert names["fusion.2"] == {BWD + "dot_general"}   # none of its own
    assert names["fusion.3"] == {FWD + "tanh", BWD + "mul"}
    assert names["add.7"] == {STEP + "add"}
    assert "copy.1" not in names and "a" not in names
    assert scopes.own_op_names(HAND_TEXT)["fusion.1"] == FWD + "dot_general"
    assert "fusion.2" not in scopes.own_op_names(HAND_TEXT)


def test_phases_of_the_hand_text():
    got = scopes.phases(HAND_TEXT)
    assert {n: got[n] for n in EXPECTED_PHASES} == EXPECTED_PHASES


def test_phase_ns_and_kernel_ns_of_the_hand_trace():
    trace = _hand_trace()
    assert scopes.phase_ns(trace, HAND_TEXT) == {
        "forward": 400, "backward": 750, "exchange": 40, "update": 30,
        "mixed": 20, "none": 15}
    assert sum(sorted(scopes.phase_ns(trace, HAND_TEXT).values())) == \
        trace_reduce.mean_busy_ns(trace) == 1255
    assert scopes.kernel_ns(trace, HAND_TEXT, "hvd_flash_fwd") == 300
    assert scopes.kernel_ns(trace, HAND_TEXT, "hvd_flash_bwd_dq") == 500
    assert scopes.kernel_ns(trace, HAND_TEXT, "hvd_flash_bwd_dkv") is None
    # A name is matched whole: "hvd_flash" is no kernel's.
    assert scopes.kernel_ns(trace, HAND_TEXT, "hvd_flash") is None


def test_phase_ns_is_a_union_per_device_and_a_mean_over_devices():
    trace = _hand_trace()
    # A second device on which the two forward operations overlap.
    trace.devices["/device:TPU:1"] = [("fusion.1", 0, 100),
                                      ("hvd_flash_fwd.1", 50, 100)]
    got = scopes.phase_ns(trace, HAND_TEXT)
    assert got["forward"] == (400 + 150) / 2
    assert got["backward"] == 750 / 2
    assert scopes.kernel_ns(trace, HAND_TEXT, "hvd_flash_fwd") == 200


def test_module_path():
    assert scopes.module_path(BWD + "dot_general", 2) == "layer_0/Dense_0"
    assert scopes.module_path(BWD + "dot_general", 1) == "layer_0"
    assert scopes.module_path(STEP + "hvd.update/add", 2) == "(top)"
    assert scopes.module_path(
        STEP + "jvp(Net)/layer_0/Attn_0/hvd_flash_fwd/pallas_call",
        3) == "layer_0/Attn_0/hvd_flash_fwd"


@pytest.mark.parametrize("name,expected", [
    ("model_forward_ms", 400 / 2 / 1e6),
    ("model_backward_ms", 750 / 2 / 1e6),
    ("optimizer_update_ms", 30 / 2 / 1e6),
    ("model_unscoped_ms", (20 + 15) / 2 / 1e6),
    ("kernels_flash_fwd_ms", 300 / 2 / 1e6),
    ("kernels_flash_bwd_dq_ms", 500 / 2 / 1e6),
    ("kernels_flash_bwd_dkv_ms", None),
])
def test_device_readers_on_the_hand_trace(name, expected):
    got = _read(name, _run(steps=2))
    assert got == (None if expected is None else pytest.approx(expected))


def test_device_readers_with_nothing_to_read_return_nothing():
    names = ("model_forward_ms", "model_backward_ms", "optimizer_update_ms",
             "model_unscoped_ms", "kernels_flash_fwd_ms",
             "kernels_flash_bwd_dq_ms", "kernels_flash_bwd_dkv_ms")
    for run in (_run(trace=None),                       # an untraced run
                _run(trace=Trace({}, [], {}, set()))):  # no device plane
        for name in names:
            assert _read(name, run) is None
    # The scope is planted and XLA fused all of it with another phase
    # (here: no operation of the update alone ran): 0, not nothing.
    fused = _hand_trace()
    fused.devices["/device:TPU:0"] = [
        e for e in fused.devices["/device:TPU:0"] if e[0] != "fusion.4"]
    assert scopes.phases_planted(HAND_TEXT) == {
        "forward", "backward", "exchange", "update", "none"}
    assert _read("optimizer_update_ms", _run(trace=fused)) == 0.0
    # A program that plants no scope of its own (an older commit): jax's
    # marks still read, the program's do not, and nothing raises.
    bare = HAND_TEXT.replace("hvd.update/", "").replace(
        "/hvd_flash_fwd/", "/").replace("/hvd_flash_bwd_dq/", "/")
    run = _run(compiled_text=bare)
    assert _read("optimizer_update_ms", run) is None
    assert _read("kernels_flash_fwd_ms", run) is None
    assert _read("kernels_flash_bwd_dq_ms", run) is None
    assert _read("model_forward_ms", run) == pytest.approx(400 / 1e6)
    # The update's 30 ns now read as nobody's, beside add.7 and copy.1.
    assert _read("model_unscoped_ms", run) == pytest.approx(45 / 1e6)


# -------------------------------------------------- the program's own log

S = 1_000_000_000
SPANS = [
    ("import", 1 * S, 3 * S, None),
    ("compile_cache.enable", 3 * S, 3 * S + 1000, None),
    ("init.distributed", 4 * S, 4 * S + 10, "init"),
    ("init.backend", 4 * S + 10, 9 * S, "init"),
    ("init", 4 * S, 9 * S + 500, None),
    ("make_mesh", 9 * S + 600, 9 * S + 700, None),
    ("replicate", 9 * S + 800, 9 * S + 900, None),
    ("shard_batch", 9 * S + 950, 9 * S + 1000, None),
    ("shard_batch", 10 * S + 5, 10 * S + 50, None),     # inside the window
]
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
EVENTS = [
    # An inner jit traced inside the outer one's trace: [5.5, 6.0] lies in
    # [5.0, 7.0]; then lowering [7.0, 7.5] and the backend [7.5, 9.5].
    (TRACE_EVENT, "multiply", 0.5, 6 * S),
    (TRACE_EVENT, "train_step", 2.0, 7 * S),
    (LOWER_EVENT, "jit(train_step)", 0.5, 7 * S + S // 2),
    ("/jax/compilation_cache/cache_misses", None, None, 9 * S),
    (BACKEND_EVENT, "jit(train_step)", 2.0, 9 * S + S // 2),
    ("/jax/compilation_cache/cache_hits", None, None, 9 * S + S // 2),
    (BACKEND_EVENT, "jit(late)", 1.0, 10 * S + S // 2),  # in the window
]


@pytest.mark.parametrize("name,expected", [
    ("import_s", 2.0),
    ("init_backend_s", 5.0 - 10e-9),
    ("placement_s", (100 + 100 + 50) * 1e-9),
    ("compile_lower_s", 2.5),       # not 3.0: the inner trace counts once
    ("compile_backend_s", 2.0),
    ("compile_cache_misses", 1),
])
def test_program_log_readers(name, expected):
    run = _run(program_spans=SPANS, program_compile_events=EVENTS)
    assert _read(name, run) == pytest.approx(expected, abs=1e-12)


def test_program_log_readers_on_a_program_that_kept_no_log():
    run = _run(program_spans=None, program_compile_events=None)
    for name in ("import_s", "init_backend_s", "placement_s",
                 "compile_lower_s", "compile_backend_s",
                 "compile_cache_misses"):
        assert _read(name, run) is None
    # It kept a log, and nothing of the kind before the window.
    run = _run(program_spans=[SPANS[0]], program_compile_events=[EVENTS[-1]])
    assert _read("init_backend_s", run) is None
    assert _read("compile_backend_s", run) == 0.0
    assert _read("compile_cache_misses", run) == 0


def test_program_log_reads_the_running_program():
    """Without a planted log the readers ask the program in this process:
    ``hvd.profiler.spans()`` holds at least the package's import."""
    import time

    import horovod_tpu as hvd

    run = _run(window={"start": time.perf_counter(), "end": 0.0})
    log = program_log.spans(run)
    assert [tuple(s) for s in hvd.profiler.spans()] == log
    assert _read("import_s", run) > 0
    assert program_log.compile_events(run) == [
        tuple(e) for e in hvd.profiler.compile_events()]


# ------------------------------------------------ recorded on the chip

def _recorded(name):
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        data = json.load(f)
    return Trace.from_json(data["trace"]), data["compiled_text"]


RECORDED = {
    # One whole step of a --trace 1 run of the cell on a TPU v5 lite
    # (PR 24): events, then ns of each phase; their sum is the busy time.
    "resnet50-dp1.scope-head.json.gz": (3601, {
        "forward": 31_153_632, "backward": 39_354_012, "update": 1_737,
        "mixed": 24_349_588, "none": 3_666_673}),
    "bert-base-s512-dp1.scope-head.json.gz": (5802, {
        "forward": 99_023_134, "backward": 159_281_516, "update": 710_078,
        "mixed": 43_549_607, "none": 3_445_771}),
}
# ns of the 12 calls a step of each flash kernel in the BERT head; the
# three together are every Mosaic call of the step.
RECORDED_KERNELS = {"hvd_flash_fwd": 45_228_481,
                    "hvd_flash_bwd_dq": 33_398_864,
                    "hvd_flash_bwd_dkv": 54_837_319}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_step_by_phase(name):
    trace, text = _recorded(name)
    count, expected = RECORDED[name]
    events = trace.devices["/device:TPU:0"]
    assert len(events) == count
    got = scopes.phase_ns(trace, text)
    assert got == expected
    # One chip runs one operation at a time: the phases add up to busy.
    assert sum(sorted(got.values())) == trace_reduce.busy_ns(events)
    # What no phase explains is XLA's own: the copies and async waits it
    # put in carry no op_name at all; every operation that carries one
    # falls in a phase.
    explained = scopes.op_names(text)
    assert sum(d for n, _, d in events
               if n not in explained) == got["none"]
    assert {trace.opcodes[n].split("-")[0] for n, _, _ in events
            if n not in explained} <= {"copy", "async", "broadcast",
                                       "custom", "iota", "bitcast"}
    run = {"trace": trace, "compiled_text": text, "steps": 1}
    for reader, phases in (("model_forward_ms", ("forward",)),
                           ("model_backward_ms", ("backward",)),
                           ("optimizer_update_ms", ("update",)),
                           ("model_unscoped_ms", ("none", "mixed"))):
        assert _read(reader, run) == pytest.approx(
            sum(expected[p] for p in phases) / 1e6)
    total = sum(_read(r, run) for r in (
        "model_forward_ms", "model_backward_ms", "optimizer_update_ms",
        "model_unscoped_ms"))
    assert total == pytest.approx(_read("model_device_ms", run), rel=1e-9)


@pytest.mark.parametrize("kernel", sorted(RECORDED_KERNELS))
def test_recorded_flash_kernels_by_name(kernel):
    trace, text = _recorded("bert-base-s512-dp1.scope-head.json.gz")
    events = trace.devices["/device:TPU:0"]
    assert len(scopes.kernel_names(trace, text, kernel)) == 12
    assert scopes.kernel_ns(trace, text, kernel) == RECORDED_KERNELS[kernel]
    # A second way to the same number: XLA names the custom call after
    # the last scope of its op_name, "<kernel>.<n>".
    assert sum(d for n, _, d in events
               if n.rsplit(".", 1)[0] == kernel) == RECORDED_KERNELS[kernel]
    run = {"trace": trace, "compiled_text": text, "steps": 1}
    assert _read(f"kernels_{kernel[4:]}_ms", run) == pytest.approx(
        RECORDED_KERNELS[kernel] / 1e6)


def test_recorded_flash_kernels_are_all_of_the_mosaic_time():
    trace, text = _recorded("bert-base-s512-dp1.scope-head.json.gz")
    run = {"trace": trace, "compiled_text": text, "steps": 1}
    assert _read("kernels_mosaic_ms", run) == pytest.approx(
        sum(sorted(RECORDED_KERNELS.values())) / 1e6, rel=1e-12)
    # ...and a cell with no kernel reads none.
    trace, text = _recorded("resnet50-dp1.scope-head.json.gz")
    for kernel in RECORDED_KERNELS:
        assert scopes.kernel_ns(trace, text, kernel) is None


def test_recorded_four_chip_step_has_an_exchange_phase():
    """One step of ``resnet50-dp4`` on every device: the combined
    all-reduce is the exchange, the update rides in the epilogue of its
    averaging (``mixed``), and what one chip reads as weight-gradient
    fusions with the update inside reads as backward here."""
    trace, text = _recorded("resnet50-dp4.scope-head.json.gz")
    assert [len(trace.devices[d]) for d in sorted(trace.devices)] == [3044] * 4
    got = scopes.phase_ns(trace, text)
    assert got == {"forward": 31_199_900.0, "backward": 63_384_866.5,
                   "exchange": 1_785_631.75, "mixed": 533_442.25,
                   "none": 3_888_181.5}
    # Nothing overlaps: the phases add up to the mean busy time.
    assert sum(sorted(got.values())) == trace_reduce.mean_busy_ns(trace)
    by_name = scopes.phases(text)
    for device in sorted(trace.devices):
        assert [n for n, _, _ in trace.devices[device]
                if by_name.get(n) == "exchange"] == ["all-reduce"]
    assert "update" in scopes.phases_planted(text)
    run = {"trace": trace, "compiled_text": text, "steps": 1}
    assert _read("optimizer_update_ms", run) == 0.0
    assert _read("model_unscoped_ms", run) == pytest.approx(
        (533_442.25 + 3_888_181.5) / 1e6)
    assert _read("model_backward_ms", run) == pytest.approx(63.3848665)


@pytest.mark.parametrize("name,expected", [
    # as the traced run on the chip printed them (PR 24, resnet50-dp4)
    ("import_s", 4.092161576),
    ("init_backend_s", 18.544816065),
    ("compile_lower_s", 10.026358127593994),
    ("compile_backend_s", 49.766579151153564),
    ("compile_cache_misses", 2),
    ("placement_s", 0.255036762),
])
def test_program_log_readers_on_a_recorded_log(name, expected):
    with open(os.path.join(FIXTURES,
                           "resnet50-dp4.program-log.json")) as f:
        run = json.load(f)
    assert len(run["program_spans"]) == 10
    assert len(run["program_compile_events"]) == 138
    assert _read(name, run) == pytest.approx(expected, rel=1e-12)
    # The step's own three records are there by name, once each.
    step = [e for e in run["program_compile_events"]
            if e[1] in ("train_step", "jit(train_step)")]
    assert [e[0].rsplit("/", 1)[-1] for e in step] == [
        "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
        "backend_compile_duration"]
