"""``harness/exchange.py`` and the four readers PR 34 built on it
(``exchange_ms``, ``exchange_exposed_ms``, ``exchange_backward_left_pct``,
``exchange_asked_mb``) on a compiled text and traces small enough to work
by hand, on the heads recorded on the chip (one whole step of every
device with the slice of the compiled text that explains it:
``tools/scope_table.py --head``) with the exchange records the program
kept in those runs, and on the program itself: the BERT builder's step,
lowered for four devices, leaves the record the reader reads."""

import gzip
import json
import os

import pytest

from harness import exchange, manifest, program_log, scopes
from harness.trace_reduce import Trace

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
NEW_METRICS = ("exchange_ms", "exchange_exposed_ms",
               "exchange_backward_left_pct", "exchange_asked_mb")
CELLS = ["resnet50-dp4", "bert-base-s512-dp4"]

STEP = "jit(train_step)/shard_map/"
FWD = STEP + "jvp(Net)/layer_0/"
BWD = STEP + "transpose(jvp(Net))/layer_0/"
ALLREDUCE = STEP + "hvd.exchange/hvd.allreduce.DistributedOptimizer.0/"

# A compiled step cut to what the parser reads. Backward work: two
# fusions and a weight gradient with the optimizer's update in its
# epilogue (mixed, a backward instruction inside). The exchange: a cast,
# the all-reduce, and its averaging. The averaging fused with the update
# (mixed, no backward instruction inside) is nobody's backward work.
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_forward (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %m.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{FWD}dot_general"}}
}}

%fused_backward (p0: f32[8]) -> f32[8] {{
  %p0.1 = f32[8]{{0}} parameter(0)
  ROOT %m.2 = f32[8]{{0}} multiply(%p0.1, %p0.1), metadata={{op_name="{BWD}dot_general"}}
}}

%fused_backward_too (p0: f32[8]) -> f32[8] {{
  %p0.2 = f32[8]{{0}} parameter(0)
  ROOT %m.3 = f32[8]{{0}} multiply(%p0.2, %p0.2), metadata={{op_name="{BWD}mul"}}
}}

%fused_gradient_and_update (p0: f32[8]) -> f32[8] {{
  %p0.3 = f32[8]{{0}} parameter(0)
  %g.1 = f32[8]{{0}} multiply(%p0.3, %p0.3), metadata={{op_name="{BWD}transpose"}}
  ROOT %a.1 = f32[8]{{0}} add(%g.1, %p0.3), metadata={{op_name="{STEP}hvd.update/add"}}
}}

%fused_average_and_update (p0: f32[8]) -> f32[8] {{
  %p0.4 = f32[8]{{0}} parameter(0)
  %d.1 = f32[8]{{0}} divide(%p0.4, %p0.4), metadata={{op_name="{ALLREDUCE}div"}}
  ROOT %a.2 = f32[8]{{0}} add(%d.1, %p0.4), metadata={{op_name="{STEP}hvd.update/add"}}
}}

%region_add (x: f32[], y: f32[]) -> f32[] {{
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y), metadata={{op_name="{ALLREDUCE}psum"}}
}}

ENTRY %main.9 (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_forward
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_backward
  %fusion.3 = f32[8]{{0}} fusion(%fusion.2), kind=kLoop, calls=%fused_backward_too
  %fusion.4 = f32[8]{{0}} fusion(%fusion.3), kind=kLoop, calls=%fused_gradient_and_update
  %convert.1 = bf16[8]{{0}} convert(%fusion.4), metadata={{op_name="{STEP}hvd.exchange/convert_element_type"}}
  %all-reduce.1 = f32[8]{{0}} all-reduce(%convert.1), replica_groups={{{{0,1,2,3}}}}, to_apply=%region_add, metadata={{op_name="{ALLREDUCE}psum"}}
  %divide.1 = f32[8]{{0}} divide(%all-reduce.1, %all-reduce.1), metadata={{op_name="{ALLREDUCE}div"}}
  %fusion.5 = f32[8]{{0}} fusion(%divide.1), kind=kLoop, calls=%fused_average_and_update
  ROOT %copy.1 = f32[8]{{0}} copy(%fusion.5)
}}
'''

EXCHANGE = {"convert.1", "all-reduce.1", "divide.1"}
BACKWARD = {"fusion.2", "fusion.3", "fusion.4"}
# Durations in ns: 750 of backward work (300 + 250 + 200), 100 of
# exchange (10 + 80 + 10).
NS = {"fusion.1": 400, "fusion.2": 300, "fusion.3": 250, "fusion.4": 200,
      "convert.1": 10, "all-reduce.1": 80, "divide.1": 10, "fusion.5": 30,
      "copy.1": 5}
AFTER = ["fusion.1", "fusion.2", "fusion.3", "fusion.4", "convert.1",
         "all-reduce.1", "divide.1", "fusion.5", "copy.1"]
# The same step with the exchange begun early: its first operation
# starts when 300 of the 750 ns of backward work are still to come.
EARLY = ["fusion.1", "fusion.2", "fusion.3", "convert.1", "all-reduce.1",
         "fusion.4", "divide.1", "fusion.5", "copy.1"]
EARLY_NS = {**NS, "fusion.3": 150, "fusion.4": 300}


def _one_after_another(order, ns=NS, at=1000, steps=1):
    events = []
    for _ in range(steps):
        for name in order:
            events.append((name, at, ns[name]))
            at += ns[name]
        at += 7     # the device idles between two steps
    return events


def _run(*device_events, steps=1, **over):
    devices = {f"/device:TPU:{i}": list(events)
               for i, events in enumerate(device_events)}
    run = {"trace": Trace(devices, [], {}, set()), "steps": steps,
           "chips": len(devices), "compiled_text": HAND_TEXT,
           "window": {"start": 10.0, "end": 11.0, "dispatch": []}}
    run.update(over)
    return run


def _read(name, run):
    return manifest.load_module("layer_metrics", name).read(run)


# ------------------------------------------------------ the hand-made step

def test_exchange_and_backward_operations_of_the_hand_text():
    phases = scopes.phases(HAND_TEXT)
    assert phases["fusion.4"] == phases["fusion.5"] == scopes.MIXED
    # Of the operations a device runs (a fusion's inner instructions
    # carry a phase too, and never show in a trace):
    found, backward = exchange.operations(HAND_TEXT)
    assert (found & set(NS), backward & set(NS)) == (EXCHANGE, BACKWARD)


def test_an_exchange_after_the_last_backward_operation_leaves_nothing():
    run = _run(_one_after_another(AFTER))
    assert _read("exchange_backward_left_pct", run) == 0.0
    assert _read("exchange_ms", run) == pytest.approx(100 / 1e6)
    # Nothing else runs beside it: all of it is waited for.
    assert _read("exchange_exposed_ms", run) == pytest.approx(100 / 1e6)
    assert _read("collective_exposed_ms", run) is None   # no opcodes here


def test_an_exchange_that_starts_with_300_of_750_ns_to_come_reads_40():
    run = _run(_one_after_another(EARLY, EARLY_NS))
    assert _read("exchange_backward_left_pct", run) == pytest.approx(40.0)
    # An operation that straddles the exchange's start counts for the
    # part after it: the all-reduce begun 50 ns into the last 350.
    events = [("fusion.2", 0, 400), ("fusion.3", 400, 350),
              ("all-reduce.1", 450, 80)]
    assert exchange.backward_left(events, EXCHANGE, BACKWARD) == 300 / 750
    assert _read("exchange_backward_left_pct", _run(events)) == \
        pytest.approx(40.0)


def test_exposure_with_another_phase_over_half_of_the_all_reduce():
    # The all-reduce runs 80 ns from 1000; a backward fusion lies over
    # its second half; the cast before it and the averaging after it run
    # alone.
    events = [("convert.1", 990, 10), ("all-reduce.1", 1000, 80),
              ("fusion.4", 1040, 200), ("divide.1", 1240, 10)]
    run = _run(events)
    assert _read("exchange_ms", run) == pytest.approx(100 / 1e6)
    assert _read("exchange_exposed_ms", run) == pytest.approx(60 / 1e6)
    # Operations of the exchange that overlap one another count once.
    events.append(("divide.1", 1000, 20))
    assert _read("exchange_ms", _run(events)) == pytest.approx(100 / 1e6)


def test_the_worst_device_is_read_and_steps_divide():
    slow = dict(NS, **{"all-reduce.1": 180})
    run = _run(_one_after_another(AFTER, steps=2),
               _one_after_another(AFTER, slow, steps=2), steps=2)
    assert _read("exchange_ms", run) == pytest.approx(200 / 1e6)
    assert _read("exchange_exposed_ms", run) == pytest.approx(200 / 1e6)


def test_steps_are_told_apart_in_the_devices_own_event_list():
    # Three steps; a loop's body (fusion.2) runs twice a step and cannot
    # open one. The first step begins the exchange late, the others
    # early: the mean is over steps, then devices.
    late = _one_after_another(AFTER[:2] + AFTER[1:], steps=1)
    end = late[-1][1] + late[-1][2]
    early = _one_after_another(EARLY[:2] + EARLY[1:], EARLY_NS,
                               at=end + 7, steps=2)
    steps = exchange.split_steps(late + early, 3)
    assert [len(s) for s in steps] == [10, 10, 10]
    assert [s[0][0] for s in steps] == ["fusion.1"] * 3
    # early: 300 left of 300 + 300 + 150 + 300 backward ns.
    assert _read("exchange_backward_left_pct",
                 _run(late + early, steps=3)) == pytest.approx(
        100 * (0 + 2 * 300 / 1050) / 3)
    # A trace that holds fewer steps than the window counted: no name
    # occurs that often, and the rarest count stands in.
    assert exchange.split_steps(late + early, 5) == steps
    # What precedes the first opening is left out.
    assert exchange.split_steps(early[-3:] + late + early, 3) == steps


@pytest.mark.parametrize("name", NEW_METRICS[:3])
def test_device_readers_with_nothing_to_read_return_nothing(name):
    assert _read(name, _run(trace=None)) is None            # untraced
    assert _read(name, _run()) is None                      # no device
    # A program that plants no exchange scope (one chip, or an older
    # commit): nothing, and nothing raises.
    bare = HAND_TEXT.replace("hvd.exchange/", "").replace(
        "hvd.allreduce.DistributedOptimizer.0/", "")
    assert _read(name, _run(_one_after_another(AFTER),
                            compiled_text=bare)) is None


def test_a_planted_exchange_that_xla_fused_away_reads_zero():
    events = [e for e in _one_after_another(AFTER) if e[0] not in EXCHANGE]
    run = _run(events)
    assert _read("exchange_ms", run) == 0.0
    assert _read("exchange_exposed_ms", run) == 0.0
    assert _read("exchange_backward_left_pct", run) is None


# --------------------------------------------------- the program's record

S = 1_000_000_000
RECORDS = [
    {"prefix": "DistributedGrad", "axis": "data", "axis_size": 4,
     "leaves": 2, "bytes_asked": 80, "bytes_wire": 80,
     "wire_dtypes": {"float32": 80}, "average": True, "at_ns": 5 * S,
     "parent": None},
    {"prefix": "DistributedOptimizer", "axis": "data", "axis_size": 4,
     "leaves": 199, "bytes_asked": 4_000_000, "bytes_wire": 2_000_000,
     "wire_dtypes": {"bfloat16": 2_000_000}, "average": True,
     "at_ns": 9 * S, "parent": None},
    {"prefix": "Late", "axis": "data", "axis_size": None, "leaves": 1,
     "bytes_asked": 4, "bytes_wire": 4, "wire_dtypes": {"float32": 4},
     "average": True, "at_ns": 10 * S + 1, "parent": None},  # in the window
]


def test_asked_reads_the_newest_record_before_the_window(capsys):
    run = _run(program_exchanges=RECORDS)
    assert exchange.record_of_the_step(run) == RECORDS[1]
    assert _read("exchange_asked_mb", run) == 2.0
    said = capsys.readouterr().out
    assert said.startswith("[exchange] ") and json.loads(
        said[len("[exchange] "):]) == RECORDS[1]


def test_asked_on_a_program_that_kept_no_record():
    assert _read("exchange_asked_mb", _run(program_exchanges=None)) is None
    assert _read("exchange_asked_mb", _run(program_exchanges=[])) is None
    assert _read("exchange_asked_mb",
                 _run(program_exchanges=RECORDS[2:])) is None


def test_asked_reads_the_running_program():
    """Without planted records the reader asks the program in this
    process, as ``program_log`` asks it for its spans."""
    import time

    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd

    tx = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="data")
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    jax.jit(lambda p: tx.update(p, tx.init(p), p)).lower(params)
    run = _run(window={"start": time.perf_counter()})
    assert exchange.records(run) == [
        r._asdict() for r in hvd.profiler.exchanges()]
    assert exchange.record_of_the_step(run)["axis_size"] is None
    assert _read("exchange_asked_mb", run) == 80 / 1e6
    assert program_log.window_start_ns(run) >= \
        exchange.records(run)[-1]["at_ns"]


def test_the_bert_step_lowered_for_four_devices_leaves_its_record():
    """What the cell's traced run reads: one record of the optimizer's
    exchange, every gradient leaf, the parameters' bytes on the wire."""
    import jax
    import numpy as np

    import horovod_tpu as hvd

    cell = manifest.Cell("bert-base-s512-dp4", rehearsal=True)
    builder = manifest.load_module("builders", cell.config["builder"])
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:cell.chips]),
                             ("data",))
    bench = builder.build(cell.config, cell.traffic, mesh)
    mark = len(hvd.profiler.exchanges())
    bench.step.lower(*bench.arg_shapes())
    [record] = hvd.profiler.exchanges()[mark:]
    leaves = jax.tree.leaves(
        jax.eval_shape(bench.weight_params, bench.weight_shapes))
    nbytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
    assert record._replace(at_ns=0) == hvd.profiler.ExchangeRecord(
        prefix="DistributedOptimizer", axis="data", axis_size=4,
        leaves=len(leaves), bytes_asked=nbytes, bytes_wire=nbytes,
        wire_dtypes={"float32": nbytes}, average=True, at_ns=0, parent=None)


# ------------------------------------------------------------ the manifest

def test_the_manifest_lists_the_metrics_in_both_four_chip_cells():
    m = manifest.load_manifest()
    by_name = {p["name"]: p for p in m["per_layer"]}
    layer = by_name["collective_exposed_ms"]["layer"]
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == CELLS
        assert by_name[name]["layer"] == layer
        assert by_name[name]["moves"] == "train_samples_per_s_per_chip"
    assert [p["name"] for p in m["per_layer"][-4:]] == list(NEW_METRICS)
    assert [by_name[n]["better"] for n in NEW_METRICS] == [
        "lower", "lower", "higher", "lower"]
    assert [by_name[n]["source"] for n in NEW_METRICS] == [
        "device_trace"] * 3 + ["program_counter"]
    # The accepted metric that selects by opcode keeps its one cell.
    assert by_name["collective_exposed_ms"]["workloads"] == CELLS[:1]
    new = m["workloads"][-1]
    assert new == {**new, "name": CELLS[1], "config": "bert-base",
                   "traffic": "mlm-s512-b64-dp4", "chips": 4}
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == CELLS


def test_the_new_traffic_is_the_one_chip_cells_but_for_name_and_who():
    one, four = (manifest.load_json(manifest.BENCH_DIR, "traffic",
                                    f"mlm-s512-b64-dp{n}.json")
                 for n in (1, 4))
    assert four["name"] == "mlm-s512-b64-dp4" and four["who"] != one["who"]
    assert {**four, "name": 0, "who": 0} == {**one, "name": 0, "who": 0}
    cell = manifest.Cell(CELLS[1])
    assert cell.chips == 4 and set(NEW_METRICS) <= {
        p["name"] for p in cell.per_layer}
    # Every per-layer metric with no list of its own applies here too.
    m = manifest.load_manifest()
    assert {p["name"] for p in m["per_layer"] if "workloads" not in p} <= {
        p["name"] for p in cell.per_layer}


# ------------------------------------------------ recorded on the chip

def _recorded(cell):
    with gzip.open(os.path.join(
            FIXTURES, f"{cell}.scope-head.json.gz"), "rt") as f:
        data = json.load(f)
    with open(os.path.join(FIXTURES, f"{cell}.exchange-log.json")) as f:
        log = json.load(f)
    return {"trace": Trace.from_json(data["trace"]),
            "compiled_text": data["compiled_text"], "steps": 1,
            "window": log["window"],
            "program_exchanges": log["program_exchanges"]}, log["printed"]


# One whole step of a --trace 1 run of the cell on four TPU v5 lite chips:
# events a device; the phases' ns (``scopes.phase_ns``, a mean over the
# devices); the exchange operations of device 0 with their ns; what the
# readers give on the head itself, the worst device's.
RECORDED = {
    "resnet50-dp4": (3044, {
        "forward": 31_199_900.0, "backward": 63_384_866.5,
        "exchange": 1_785_631.75, "mixed": 533_442.25, "none": 3_888_181.5},
        [("all-reduce", 1_794_746)],
        {"exchange_ms": 1.794746, "exchange_exposed_ms": 1.794746,
         "exchange_backward_left_pct": 0.0,
         "exchange_asked_mb": 102.228128}),
    "bert-base-s512-dp4": (3628, {
        "forward": 52_824_411.75, "backward": 113_351_509.25,
        "exchange": 9_185_850.25, "mixed": 4_497_867.0,
        "none": 4_669_371.75},
        [("psum.1595", 1_640_358), ("all-reduce.1", 2_179_447),
         ("psum.1597", 1_622_046), ("all-reduce.2", 1_577_599),
         ("all-reduce", 2_175_217)],
        {"exchange_ms": 9.194667, "exchange_exposed_ms": 9.194667,
         "exchange_backward_left_pct": 82.47452911530226,
         "exchange_asked_mb": 529.452264}),
}


@pytest.mark.parametrize("cell", CELLS)
def test_recorded_four_chip_step(cell):
    run, _ = _recorded(cell)
    trace, text = run["trace"], run["compiled_text"]
    count, phases, on_device_0, expected = RECORDED[cell]
    assert [len(trace.devices[d]) for d in sorted(trace.devices)] == \
        [count] * 4
    assert scopes.phase_ns(trace, text) == phases
    found, backward = exchange.operations(text)
    events = trace.devices["/device:TPU:0"]
    assert [(n, d) for n, _, d in events if n in found] == on_device_0
    # Every operation of the exchange is an all-reduce here: the casts
    # there are none of, and the averaging went into the update's fusions.
    assert {trace.opcodes[n] for n, _ in on_device_0} == {"all-reduce"}
    assert exchange.split_steps(events, 1) == [events]
    for name, value in sorted(expected.items()):
        assert _read(name, run) == pytest.approx(value, rel=1e-12)
    # Nothing runs beside a synchronous all-reduce: all of it is exposed,
    # and the reader that selects by opcode agrees with the one that
    # selects by scope.
    assert _read("collective_exposed_ms", run) == _read("exchange_ms", run)


@pytest.mark.parametrize("cell", CELLS)
def test_recorded_step_against_what_the_traced_run_printed(cell):
    """The head is one step of the window's 19 (BERT) or of another run's
    (ResNet: the head is PR 24's, the printed line PR 34's); the line is
    the whole window's. They agree to the step-to-step spread."""
    run, printed = _recorded(cell)
    for name in NEW_METRICS[:2]:
        assert _read(name, run) == pytest.approx(printed[name], rel=0.01)
    assert _read("exchange_backward_left_pct", run) == pytest.approx(
        printed["exchange_backward_left_pct"], abs=0.01)
    assert _read("exchange_asked_mb", run) == printed["exchange_asked_mb"]
    # What was asked for against what XLA made of it.
    assert printed["exchange_asked_mb"] == pytest.approx(
        printed["collective_payload_mb"], rel=1e-3)
    # (The head's text slice keeps the instructions a device ran, the
    # all-reduces among them: enough for ``hlo_text.collectives``.)
    assert _read("collective_payload_mb", run) == \
        printed["collective_payload_mb"]


def test_recorded_bert_step_exchanges_in_five_all_reduces():
    """What the cell was added to show (PR 34): XLA makes five
    synchronous all-reduces of the 199 leaves, begins with the head's
    kernel when 82% of the backward pass is still to come, and nothing
    runs beside any of them."""
    run, printed = _recorded("bert-base-s512-dp4")
    [record] = run["program_exchanges"]
    assert (record["leaves"], record["axis_size"]) == (199, 4)
    found, backward = exchange.operations(run["compiled_text"])
    events = run["trace"].devices["/device:TPU:0"]
    left = [100 * exchange.backward_left(
        [e for e in events if e[0] in backward or e[0] == name],
        {name}, backward) for name, _ in RECORDED["bert-base-s512-dp4"][2]]
    assert [round(x, 2) for x in left] == [82.47, 15.06, 0.0, 0.0, 0.0]
    # 529.45 MB asked for; the loss's four bytes ride with one of them.
    assert printed["collective_payload_mb"] * 1e6 == \
        record["bytes_wire"] + 4
    assert printed["collective_count"] == 5
