"""The Ouro cell (PR 48) rehearsed on the CPU, and the readers and
work-counting functions it brought, on hand counts and a hand-made run.

``run.py --rehearse-cpu`` end to end in a child process, traced, at the
tiny sizes the configuration and traffic files give (one sequence of 256,
two layers run the published four times, hidden 64, a vocabulary of 512
through the untied head, the four exits in one sweep of two chunks). The
broken steps are ``test_control_ouro.py``."""

import json
import math
import os
import re
import subprocess
import sys

import pytest

from harness import manifest
from harness.trace_reduce import Trace

CELL = "ouro-2.6b-s8k-loop4"
RUN = os.path.join(manifest.BENCH_DIR, "run.py")
NEW_METRICS = ("loop_pass_ms", "loop_exit_ms", "loop_head_ms",
               "loop_head_roofline", "attn_loop_flash_ms",
               "attn_loop_flash_roofline", "loop_exit_entropy")
MODELS, KERNELS = ("models: models/resnet.py, models/bert.py",
                   "kernels: ops/attention.py")
LAYER_OF = {**dict.fromkeys(NEW_METRICS, MODELS),
            **dict.fromkeys(NEW_METRICS[4:6], KERNELS)}


def test_rehearsal_runs_traced_and_is_marked():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483659",
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=300)
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
    # The program returned the exits' numbers with its state: the first
    # and the last checked step's mean exit distribution, each summing to
    # 1 over the four exits, and an entropy under ln 4 that the three
    # steps have not shut.
    line = next(ln for ln in lines if ln.startswith("[loop] "))
    first, last = (
        [float(v) for v in part.split(", ")] for part in re.search(
            r"first checked step: ([-\d.e, ]+); last: ([-\d.e, ]+) \(",
            line).groups())
    for numbers in (first, last):
        assert len(numbers) == 5
        assert sum(numbers[:4]) == pytest.approx(1.0, abs=1e-4)
        assert all(p > 0.02 for p in numbers[:4])
        assert 0.7 < numbers[4] < math.log(4)
    assert first != last


def test_the_cell_lists_the_new_metrics_and_no_other_cell_does():
    m = manifest.load_manifest()
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == LAYER_OF[name]
        assert by_name[name]["moves"] == "train_samples_per_s_per_chip"
    assert by_name["loop_exit_entropy"]["source"] == "program_counter"
    assert (by_name["loop_exit_entropy"]["unit"],
            by_name["loop_exit_entropy"]["better"]) == ("nats", "higher")
    for name in ("loop_head_roofline", "attn_loop_flash_roofline"):
        assert (by_name[name]["unit"], by_name[name]["better"]) == (
            "%", "higher")
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and set(NEW_METRICS) <= {
        p["name"] for p in cell.per_layer}
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"]) == (
        "ouro-2.6b", "causal-s8192-b1-dp1")
    # Every per-layer metric with no list of its own applies here too,
    # and the accepted lists were left as they were.
    assert {p["name"] for p in m["per_layer"] if "workloads" not in p} <= {
        p["name"] for p in cell.per_layer}
    for name in ("loss_head_ms", "attn_full_ms", "attn_flash_ms",
                 "attn_flash_roofline", "attn_head64_flash_ms", "mtp_ms"):
        assert CELL not in by_name[name]["workloads"]
    # Two of eleven cells take four chips: the quarter allowed was full.
    assert len(m["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in m["workloads"][:11]) == 2


def test_the_configuration_is_the_cut_it_states():
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "ouro-2.6b")
    config = manifest.Cell(CELL).config
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    # Depth is the only cut.
    assert entry["reduced"] == config["reduced"] == ["num_layers"]
    assert (config["num_layers"], config["num_hidden_layers"],
            config["published"]["num_hidden_layers"]) == (6, 48, 48)
    assert config["layer_types"] == ["full_attention"] * 48
    # Every width, the head counts, the vocabulary and the passes as
    # published.
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["vocab_size"],
            config["total_ut_steps"], config["rope_theta"],
            config["rms_norm_eps"], config["early_exit_threshold"],
            config["max_position_embeddings"], config["max_window_layers"]) \
        == (2048, 5632, 16, 16, 128, 49152, 4, 1000000, 1e-06, 1, 65536, 48)
    assert (config["model_type"], config["hidden_act"]) == ("ouro", "silu")
    assert config["rope_scaling"] is None and config["sliding_window"] is None
    assert not config["use_sliding_window"]
    assert not config["tie_word_embeddings"]
    deployment = config["deployment"]
    assert deployment["stages"] * config["num_layers"] == 48
    assert deployment["layers_held"] == list(range(6))
    for key in ("published", "deployment", "assumed", "rehearsal"):
        assert config[key]
    assert config["assumed"]["exit_entropy_beta"] == 0.1
    assert (config["compute_dtype"], config["param_dtype"], config["remat"],
            config["loss_chunks"]) == ("bfloat16", "float32", True, 8)
    assert (config["init"]["kernel"], config["init"]["embedding"],
            config["init"]["scale"], config["init"]["bias"]) == (
        0.02, 0.02, 1.0, 0.0)
    # The rate is not the issue's 1e-4: there the gate shuts three exits
    # inside the checked steps on nine seeds of ten (the readings are in
    # the file), and a uniform distribution reads ln 4 = 1.386.
    assert config["optimizer"]["learning_rate"] == 1e-6
    shut = config["assumed"]["optimizer_readings"]["1e-4"][
        "entropy_third_step_ten_seeds"]
    assert len(shut) == 10 and sum(h < 0.15 for h in shut) == 9
    # The rehearsal keeps the mechanism alive: the published four passes
    # over more than one layer.
    toy = manifest.Cell(CELL, rehearsal=True).config
    assert (toy["num_layers"], toy["total_ut_steps"], toy["head_dim"],
            toy["vocab_size"]) == (2, 4, 32, 512)


def test_parameters_add_up_as_the_configuration_says():
    """The builder's tree of shapes against ISSUE 48's arithmetic: a layer
    51,388,416, embedding and head 201,326,592, the final norm 2048, the
    gate 2049: 509,661,185 in all, whatever the number of passes."""
    import jax
    import numpy as np

    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    attention, mlp = builder.matrix_parameters(cell.config)
    assert attention == 4 * 2048 * 2048 and mlp == 3 * 2048 * 5632
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    sizes = {}
    for passes in (4, 1):
        bench = builder.build({**cell.config, "total_ut_steps": passes},
                              cell.traffic, mesh)
        sizes[passes] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                jax.eval_shape(bench.weight_params, bench.weight_shapes))[0]:
            top = str(getattr(path[0], "key", path[0]))
            sizes[passes][top] = sizes[passes].get(top, 0) + int(
                np.prod(leaf.shape))
    assert sizes[4] == sizes[1]
    sizes = sizes[4]
    assert sorted(sizes) == ["early_exit_gate", "final_norm"] + [
        f"layer_{i}" for i in range(6)] + ["lm_head", "tok_embeddings"]
    assert all(sizes[f"layer_{i}"] == attention + mlp + 4 * 2048
               == 51_388_416 for i in range(6))
    assert sizes["tok_embeddings"] == sizes["lm_head"] == 49152 * 2048
    assert (sizes["final_norm"], sizes["early_exit_gate"]) == (2048, 2049)
    assert sum(sizes[top] for top in sorted(sizes)) == 509_661_185
    assert "509,661,185" in cell.config["deployment"]["parameters_here"]


def test_work_counting_functions_against_hand_counts():
    builder = manifest.load_module("builders", "ouro_adamw")
    cell = manifest.Cell(CELL)
    c, seq = cell.config, 8192
    assert builder.block_applications(c) == 24
    pairs = builder.causal_pairs(seq)
    assert pairs == seq * (seq + 1) // 2
    # The flash kernels: 24 applications at 16 heads over 16 of width
    # 128, as flash_band_work counts one layer's; FLOPs bound them.
    flash = manifest.load_module("layer_metrics", "attn_flash_roofline")
    f, b = flash.flash_band_work(1, 16, 16, seq, 128, pairs, 2)
    assert builder.loop_flash_work(c, 1, seq, 2) == (24 * f, 24 * b)
    assert 24 * f == 24 * (8 + 6 + 8) * 16 * pairs * 128
    assert 24 * f / 197e12 > 24 * b / 819e9
    # The head: three products over the 4 x 8192 rows of the stacked
    # exits; 100.5 ms at the bf16 peak, the bytes a tenth of that.
    flops, nbytes = builder.loop_head_work(c, 1, seq)
    assert flops == 6 * 4 * seq * 2048 * 49152
    assert 0.100 < flops / 197e12 < 0.101
    assert nbytes / 819e9 < 0.1 * flops / 197e12
    # The step: every layer's matrices once a pass, the head once an
    # exit, the gate once a pass, the causal pairs once an application.
    attention, mlp = builder.matrix_parameters(c)
    met = 24 * (attention + mlp) + 4 * 2048 * (49152 + 1)
    assert builder.train_flops_per_step(c, 1, seq) == \
        6.0 * seq * met + 12.0 * 128 * 16 * pairs * 24
    assert 1.00e14 < builder.train_flops_per_step(c, 1, seq) < 1.005e14
    # Counted short by a pass, the whole step's share of the peak would
    # read a quarter high: three passes are not four.
    assert builder.train_flops_per_step(
        {**c, "total_ut_steps": 3}, 1, seq) < 0.76 * \
        builder.train_flops_per_step(c, 1, seq)


def test_the_builder_reads_the_model_from_the_configuration():
    import dataclasses

    from horovod_tpu.models import OURO_2_6B

    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    cfg = builder.model_config(cell.config)
    assert (cfg.num_layers, cfg.total_ut_steps, cfg.remat) == (6, 4, True)
    assert dataclasses.replace(cfg, num_layers=48, remat=False) == OURO_2_6B
    with pytest.raises(ValueError, match="counts the layers held"):
        builder.model_config({**cell.config, "num_layers": 8})
    # What the program's block has no other form of.
    for key, other in (("num_key_value_heads", 4),
                       ("use_sliding_window", True),
                       ("tie_word_embeddings", True),
                       ("hidden_act", "gelu"),
                       ("rope_scaling", {"rope_type": "yarn"})):
        with pytest.raises(ValueError, match="the program's block"):
            builder.model_config({**cell.config, key: other})


# ------------------------------------------------- the readers, by hand

STEP = "jit(train_step)/shard_map/"
PASS = STEP + "transpose(jvp(OuroLM))/hvd.loop.pass/layer_2/"
FULL = STEP + "jvp(OuroLM)/hvd.loop.pass/layer_1/attention/hvd.attn.full/"
EXIT = STEP + "jvp(hvd.loop.exit)/"
HEAD = STEP + "jvp(hvd.loss.head)/"
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_mlp (p0: f32[8,8]) -> f32[8,8] {{
  %p0 = f32[8,8]{{1,0}} parameter(0)
  ROOT %dot.1 = f32[8,8]{{1,0}} dot(%p0, %p0), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{PASS}w_up/dot_general"}}
}}

%fused_exits (p0: f32[8]) -> f32[8] {{
  %p0.1 = f32[8]{{0}} parameter(0)
  ROOT %s.1 = f32[8]{{0}} exponential(%p0.1), metadata={{op_name="{EXIT}exp"}}
}}

%sweep_body (p0: f32[8]) -> f32[8] {{
  %p0.2 = f32[8]{{0}} parameter(0)
  ROOT %m.1 = f32[8]{{0}} multiply(%p0.2, %p0.2), metadata={{op_name="{HEAD}while/body/mul"}}
}}

%sweep_cond (p0: f32[8]) -> pred[] {{
  %p0.3 = f32[8]{{0}} parameter(0)
  ROOT %c.1 = pred[] constant(false)
}}

ENTRY %main (a: f32[8], b: f32[8,8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %b = f32[8,8]{{1,0}} parameter(1)
  %fusion.1 = f32[8,8]{{1,0}} fusion(%b), kind=kOutput, calls=%fused_mlp
  %fusion.2 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_exits
  %gate.1 = f32[8]{{0}} convert(%a), metadata={{op_name="{STEP}jvp(OuroLM)/hvd.loop.exit/early_exit_gate/dot_general"}}
  %norm.1 = f32[8]{{0}} multiply(%a, %a), metadata={{op_name="{STEP}jvp(OuroLM)/hvd.loop.pass/final_norm/mul"}}
  %while.1 = f32[8]{{0}} while(%a), condition=%sweep_cond, body=%sweep_body, metadata={{op_name="{HEAD}while"}}
  %embed.1 = f32[8]{{0}} copy(%a), metadata={{op_name="{STEP}jvp(OuroLM)/tok_embeddings/take"}}
  %flash.1 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{FULL}hvd_flash_fwd/pallas_call"}}
  %flash.2 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{PASS}attention/hvd.attn.full/hvd_flash_bwd_dq/pallas_call"}}
  %flash.3 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}jvp(OuroLM)/layer_1/attention/hvd_flash_fwd/pallas_call"}}
  ROOT %other = f32[8]{{0}} add(%flash.1, %flash.2), metadata={{op_name="{STEP}hvd.update/add"}}
}}
'''
# One device, two steps; ns. flash.3 is a kernel under no scope of the
# loop's; embed.1 and other are outside every pass.
HAND_EVENTS = [("fusion.1", 0, 900), ("fusion.2", 900, 60),
               ("gate.1", 960, 40), ("norm.1", 1000, 100),
               ("while.1", 1100, 2000), ("embed.1", 3100, 30),
               ("flash.1", 3130, 400), ("flash.2", 3530, 600),
               ("flash.3", 4130, 200), ("other", 4330, 50)]
EXITS = [[0.4, 0.3, 0.2, 0.1, 1.25], [0.5, 0.25, 0.125, 0.125, 1.2130]]


def _hand_run():
    trace = Trace({"/device:TPU:0": HAND_EVENTS}, [], {},
                  {"flash.1", "flash.2", "flash.3"})
    return {"trace": trace, "compiled_text": HAND_TEXT, "steps": 2,
            "cell": manifest.Cell(CELL), "chips": 1,
            "stamp": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "loop_exits": EXITS}


def _read(name, run):
    return manifest.load_module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name,expected", [
    ("loop_pass_ms", (900 + 100 + 400 + 600) / 2 / 1e6),
    ("loop_exit_ms", (60 + 40) / 2 / 1e6),
    ("loop_head_ms", 2000 / 2 / 1e6),
    ("attn_loop_flash_ms", (400 + 600) / 2 / 1e6),
    ("loop_exit_entropy", 1.25),
])
def test_readers_on_the_hand_run(name, expected):
    assert _read(name, _hand_run()) == pytest.approx(expected)


def test_rooflines_on_the_hand_run(capsys):
    run = _hand_run()
    builder = manifest.load_module("builders", "ouro_adamw")
    config = run["cell"].config
    flops, nbytes = builder.loop_head_work(config, 1, 8192)
    assert flops / 197e12 > nbytes / 819e9
    assert _read("loop_head_roofline", run) == pytest.approx(
        100 * (flops / 197e12) / (2000 / 2 / 1e9))
    # The hand text makes one forward call under attention's scope for
    # the configuration's 24 applications.
    flops, nbytes = builder.loop_flash_work(config, 1, 8192, 1 / 24)
    assert _read("attn_loop_flash_roofline", run) == pytest.approx(
        100 * (flops / 197e12) / (1000 / 2 / 1e9))
    said = capsys.readouterr().out
    assert "[loop_head_roofline] bound by flops" in said
    assert "forward calls an application of a block; bound by flops" in said


def test_readers_return_nothing_on_a_program_without_the_scopes():
    """The parent's programs plant no such scope and keep no such
    numbers: every new reader returns ``None`` and raises nothing; so
    does a run with no device trace."""
    run = _hand_run()
    run["compiled_text"] = HAND_TEXT.replace("hvd.loop.", "loop.").replace(
        "hvd.attn.", "attn.").replace("hvd.loss.", "loss.")
    del run["loop_exits"]
    for name in NEW_METRICS:
        assert _read(name, run) is None
    untraced = dict(_hand_run(), trace=None)
    for name in NEW_METRICS[:-1]:
        assert _read(name, untraced) is None
