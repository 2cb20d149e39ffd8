"""The SmallThinker cell (PR 26) rehearsed on the CPU, and the readers it
brought on a hand-made run and on a head recorded on the chip.

``test_rehearsal.py`` names its cells in its own parametrisation; this
file does the same for ``smallthinker-21b-a3b-s8k-ep4share``: ``run.py
--rehearse-cpu`` end to end in a child process, traced, at the tiny sizes
the configuration and traffic files give (sequence 1024 so that the flash
kernels stream, a window of 384, 3 of 8 experts held, 2 chosen)."""

import gzip
import json
import os
import subprocess
import sys

import pytest

from harness import manifest, scope_time
from harness.trace_reduce import Trace

CELL = "smallthinker-21b-a3b-s8k-ep4share"
RUN = os.path.join(manifest.BENCH_DIR, "run.py")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
NEW_METRICS = ("moe_experts_ms", "moe_dispatch_ms", "moe_experts_roofline",
               "moe_load_max_over_mean", "attn_flash_ms",
               "attn_flash_roofline")


def test_rehearsal_runs_traced_and_is_marked():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483659",
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"rehearsal", "correct", "attempted", "failed",
                           "metrics", "device"}
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # No CPU timing under any metric's name, anywhere in the output.
    m = manifest.load_manifest()
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    text = "\n".join(lines)
    assert not any(name in text for name in names)
    checks = [ln for ln in lines if ln.startswith("[check] ")
              and " limit " in ln]
    assert len(checks) >= 6 and all(ln.endswith(" ok") for ln in checks)
    # The program counted what landed on the held experts, layer by layer.
    assert any(ln.startswith("[moe] assignments landed") for ln in lines)


def test_the_cell_lists_the_new_metrics_and_no_other_cell_does():
    m = manifest.load_manifest()
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and set(NEW_METRICS) <= {
        p["name"] for p in cell.per_layer}
    assert cell.config["reduced"] == [
        "num_layers", "moe_num_primary_experts", "vocab_size"]
    assert len(cell.config["deployment"]["experts_held"]) == \
        cell.config["moe_num_primary_experts"] == 16
    assert cell.config["deployment"]["router_width"] == \
        cell.config["published"]["moe_num_primary_experts"] == 64


def test_flops_count_the_band_and_the_rows_held():
    builder = manifest.load_module("builders", "smallthinker_adamw")
    assert builder.band_pairs(6) == 21
    assert builder.band_pairs(6, 3) == 6 + 3 * 3    # rows 0-2, then 3 each
    assert builder.band_pairs(6, 9) == 21           # a window past the end
    cell = manifest.Cell(CELL)
    assert builder.layer_windows(cell.config) == [None, 4096, 4096, 4096]
    assert builder.expected_rows_held(cell.config, 16384) == 24576
    # Tentpole 5's count, forward FLOPs a token a layer: experts held 18M,
    # projections 42M, attention 44-59M; the head 194M once.
    seq, c = 8192, cell.config
    experts = 2 * 3 * c["hidden_size"] * c["moe_ffn_hidden_size"] * 1.5
    assert round(experts / 1e6) == 18
    attention = [4 * c["head_dim"] * c["num_attention_heads"]
                 * builder.band_pairs(seq, w) / seq / 1e6
                 for w in builder.layer_windows(c)]
    assert [round(a) for a in attention] == [59, 44, 44, 44]
    total = builder.train_flops_per_step(c, 2, seq)
    assert 3.0e13 < total < 3.2e13


def test_lower_precision_controls_read_not_correct():
    """``test_control.py``'s control for this cell: the reference in the
    program's place one precision below bf16, int8 and fp8, fails a limit
    at the rehearsal's size; at bf16 it stays inside all of them."""
    import jax

    import horovod_tpu as hvd
    from builders import training
    from harness import compare

    hvd.init()
    cell = manifest.Cell(CELL, rehearsal=True)
    reference = manifest.load_module("reference", cell.config["reference"])
    limits = reference.REHEARSAL_LIMITS
    assert reference.CONTROL == "int8"
    builder = manifest.load_module("builders", cell.config["builder"])
    program = training.compile_program(cell, jax.devices()[:1],
                                       builder.build, {})
    steps = cell.traffic["checked_steps"]
    key, _, host_batch, _ = training.seeded_inputs(program, 2147483693)
    numbers = {precision: training.reference_numbers(
        cell, program, host_batch, key, steps, precision=precision)
        for precision in ("f32", "bf16", "int8", "fp8")}
    ref = numbers.pop("f32")
    assert compare.judge(training.gaps(numbers["bf16"], ref), limits)
    assert not compare.judge(training.gaps(numbers["int8"], ref), limits)
    assert not compare.judge(training.gaps(numbers["fp8"], ref), limits)


# ------------------------------------------------- the readers, by hand

STEP = "jit(train_step)/shard_map/"
BLOCK = STEP + "transpose(jvp(SmallThinkerLM))/layer_0/"
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_gather (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %g.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{BLOCK}hvd.moe.dispatch/gather"}}
}}

%fused_gate (p0: f32[8]) -> f32[8] {{
  %p0.1 = f32[8]{{0}} parameter(0)
  %c.1 = f32[8]{{0}} multiply(%p0.1, %p0.1), metadata={{op_name="{BLOCK}hvd.moe.combine/mul"}}
  ROOT %m.1 = f32[8]{{0}} multiply(%c.1, %p0.1), metadata={{op_name="{BLOCK}hvd.moe.experts/mul"}}
}}

ENTRY %main (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_gather
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_gate
  %top.1 = f32[8]{{0}} sort(%fusion.2), metadata={{op_name="{STEP}jvp(SmallThinkerLM)/layer_0/hvd.moe.route/top_k"}}
  %ragged-dot-none = f32[8]{{0}} custom-call(%top.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %flash.1 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{BLOCK}attention/hvd_flash_fwd/pallas_call"}}
  %flash.2 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{BLOCK}attention/hvd_flash_bwd_dq/pallas_call"}}
  ROOT %other = f32[8]{{0}} add(%flash.1, %flash.2), metadata={{op_name="{BLOCK}add"}}
}}
'''
# One device, two steps; ns. fusion.2 holds an operation of the experts'
# own and one of the combine: it counts with the experts.
HAND_EVENTS = [("fusion.1", 0, 100), ("fusion.2", 100, 300),
               ("top.1", 400, 50), ("ragged-dot-none", 450, 1000),
               ("flash.1", 1450, 400), ("flash.2", 1850, 600),
               ("other", 2450, 50)]


def _hand_run():
    trace = Trace({"/device:TPU:0": HAND_EVENTS}, [], {},
                  {"ragged-dot-none", "flash.1", "flash.2"})
    cell = manifest.Cell(CELL)
    return {"trace": trace, "compiled_text": HAND_TEXT, "steps": 2,
            "cell": cell, "chips": 1,
            "stamp": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "moe_load": [[30, 10, 20, 20], [5, 5, 5, 25]]}


def _read(name, run):
    return manifest.load_module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name,expected", [
    ("moe_experts_ms", (300 + 1000) / 2 / 1e6),
    ("moe_dispatch_ms", (100 + 50) / 2 / 1e6),
    ("attn_flash_ms", (400 + 600) / 2 / 1e6),
    ("moe_load_max_over_mean", 25 * 4 / 40),
])
def test_readers_on_the_hand_run(name, expected):
    assert _read(name, _hand_run()) == pytest.approx(expected)


def test_rooflines_on_the_hand_run(capsys):
    run = _hand_run()
    experts = manifest.load_module("layer_metrics", "moe_experts_roofline")
    flops, nbytes = experts.experts_work(120, 8, 2560, 768)
    assert flops == 9 * 2 * 2560 * 768 * 120
    assert nbytes == 3 * 3 * (120 * 3328 + 8 * 2560 * 768) * 2
    least = max(flops / 197e12, nbytes / 819e9)
    assert experts.read(run) == pytest.approx(100 * least / (1300 / 2 / 1e9))
    flash = manifest.load_module("layer_metrics", "attn_flash_roofline")
    f, b = flash.flash_band_work(2, 28, 4, 8192, 128, 100, 2)
    assert f == (8 + 6 + 8) * 2 * 28 * 100 * 128
    q, kv, stat = 2 * 28 * 8192 * 128 * 2, 2 * 4 * 8192 * 128 * 2, \
        2 * 28 * 8192 * 4
    assert b == 2 * (2 * q + 2 * kv + stat) + (3 * q + 2 * kv + 2 * stat) \
        + (2 * q + 4 * kv + 2 * stat)
    # The hand text makes one forward call for four layers.
    assert flash.read(run) > 0
    assert "0.25 forward calls a layer" in capsys.readouterr().out


def test_readers_return_nothing_on_a_program_without_the_scopes():
    """The parent's programs plant no expert scope and the other cells no
    causal band: every new reader returns ``None`` and raises nothing."""
    run = _hand_run()
    run["compiled_text"] = HAND_TEXT.replace("hvd.moe.", "moe.").replace(
        "hvd_flash", "flash")
    del run["moe_load"]
    for name in NEW_METRICS:
        assert _read(name, run) is None
    untraced = dict(_hand_run(), trace=None)
    for name in ("moe_experts_ms", "moe_dispatch_ms", "attn_flash_ms",
                 "attn_flash_roofline", "moe_experts_roofline"):
        assert _read(name, untraced) is None


def test_scope_time_counts_a_union_per_device():
    text = HAND_TEXT
    keep = scope_time.names_under(text, ("hvd.moe.dispatch",))
    assert keep == {"fusion.1", "g.1"}
    assert scope_time.names_under(text, ("hvd.moe.route",)) == {"top.1"}
    assert not scope_time.names_under(text, ("hvd.moe",))   # a whole word
    trace = Trace({"a": [("fusion.1", 0, 100), ("fusion.1", 50, 100)],
                   "b": [("fusion.1", 0, 50)]}, [], {}, set())
    run = {"trace": trace, "steps": 1}
    assert scope_time.union_ms_a_step(run, keep) == (150 + 50) / 2 / 1e6


# ------------------------------------------------ recorded on the chip

def _recorded():
    path = os.path.join(FIXTURES, CELL + ".scope-head.json.gz")
    if not os.path.exists(path):
        pytest.skip("no head of this cell was recorded on the chip")
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return Trace.from_json(data["trace"]), data["compiled_text"]


def test_recorded_step_reads_under_every_new_name():
    trace, text = _recorded()
    run = dict(_hand_run(), trace=trace, compiled_text=text, steps=1)
    for name in NEW_METRICS:
        value = _read(name, run)
        assert value is not None and value > 0, name
    assert _read("attn_flash_roofline", run) < 100
