"""The Laguna cell (PR 32) rehearsed on the CPU, and the readers and
work-counting functions it brought, on hand counts and a hand-made run.

``run.py --rehearse-cpu`` end to end in a child process, traced, at the
tiny sizes the configuration and traffic files give (two sequences of
1024 so that the flash kernels stream, a window of 384 below the key
block, both layer types, the dense layer, 3 of 8 experts held, 2 chosen,
YaRN past its original context of 256). The controls and the broken
steps are ``test_control_laguna.py``."""

import json
import os
import re
import subprocess
import sys

import pytest

from harness import manifest
from harness.trace_reduce import Trace

CELL = "laguna-xs.2-s8k-ep16share"
RUN = os.path.join(manifest.BENCH_DIR, "run.py")
NEW_METRICS = ("attn_window_ms", "attn_full_ms", "attn_window_roofline",
               "attn_pointwise_ms", "moe_shared_ms", "moe_held_ms",
               "moe_held_experts_roofline", "moe_held_load_max_over_mean")
LAYER_OF = {"attn_pointwise_ms": "models: models/resnet.py, models/bert.py",
            **dict.fromkeys(NEW_METRICS[:3], "kernels: ops/attention.py"),
            **dict.fromkeys(NEW_METRICS[4:],
                            "expert layer: parallel/moe.py moe_apply_held")}


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
    # The program counted what landed on the held experts of the four
    # sparse layers: 2 x 1024 tokens x 2 chosen x 3 of 8 held.
    line = next(ln for ln in lines if ln.startswith("[moe] "))
    landed = json.loads(re.search(r"layer: (\[[\d, ]+\])", line).group(1))
    assert len(landed) == 4 and "(expected 1536 a layer)" in line
    assert all(0.8 * 1536 < rows < 1.25 * 1536 for rows in landed)


def test_the_cell_lists_the_new_metrics_and_no_other_cell_does():
    m = manifest.load_manifest()
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == LAYER_OF[name]
        assert by_name[name]["moves"] == "train_samples_per_s_per_chip"
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and set(NEW_METRICS) <= {
        p["name"] for p in cell.per_layer}
    # Every per-layer metric with no list of its own applies here too,
    # and the accepted lists were left as they were.
    assert {p["name"] for p in m["per_layer"] if "workloads" not in p} <= {
        p["name"] for p in cell.per_layer}
    for name in ("moe_experts_ms", "moe_dispatch_ms", "attn_flash_ms",
                 "attn_flash_roofline", "loss_head_ms"):
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_is_the_share_it_states():
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "laguna-xs.2")
    config = manifest.Cell(CELL).config
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    assert entry["reduced"] == config["reduced"] == [
        "num_layers", "num_experts", "vocab_size"]
    deployment = config["deployment"]
    assert deployment["experts_held"] == list(range(16))
    assert deployment["chips_sharing_a_layer"] == 16
    assert config["num_experts"] == 16 and deployment["router_width"] == \
        config["published"]["num_experts"] == 256
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    n = config["num_layers"]
    assert config["layer_types"][:n] == [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert config["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"][:n] == [48, 64, 64, 64, 48]
    assert len(config["layer_types"]) == config["num_hidden_layers"] \
        == config["published"]["num_hidden_layers"] == 40
    # Every width as published.
    assert (config["hidden_size"], config["intermediate_size"],
            config["head_dim"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["num_key_value_heads"]) == (2048, 8192, 128, 512, 512, 8,
                                               512, 8)
    full = config["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["partial_rotary_factor"],
            full["attention_factor"]) == ("yarn", 64, 0.5,
                                          1.4158883083359672)
    for key in ("published", "deployment", "assumed", "rehearsal"):
        assert config[key]
    # The three learning rates' readings are in the file, and the rate
    # chosen is one of them.
    readings = config["assumed"]["optimizer_readings"]
    assert set(readings["rates"]) == {"1e-4", "1e-5", "1e-6"}
    assert config["optimizer"]["learning_rate"] in (1e-4, 1e-5, 1e-6)


def test_parameters_add_up_as_the_configuration_says():
    """The builder's tree of shapes against ISSUE 32's arithmetic: a full
    layer's attention 29.46M, a sliding one's 37.88M, the dense MLP
    50.33M, a routed or shared expert 3.146M, the router 0.524M, 490.3M
    in all."""
    import jax
    import numpy as np

    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    full, sliding, mlp, shared, router, expert = \
        builder.matrix_parameters(cell.config)
    assert full == 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    assert sliding == 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    assert mlp == 3 * 2048 * 8192 and shared == expert == 3 * 2048 * 512
    assert router == 2048 * 256
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    bench = builder.build(cell.config, cell.traffic, mesh)
    sizes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(bench.weight_params, bench.weight_shapes))[0]:
        top = str(getattr(path[0], "key", path[0]))
        sizes[top] = sizes.get(top, 0) + int(np.prod(leaf.shape))
    assert round(sum(sizes[top] for top in sorted(sizes)) / 1e6, 1) == 490.3
    norms = 2 * 2048
    assert sizes["layer_0"] == full + mlp + norms
    assert sizes["layer_1"] == sizes["layer_3"] == \
        sliding + 16 * expert + shared + router + norms
    assert sizes["layer_4"] == full + 16 * expert + shared + router + norms
    assert sizes["tok_embeddings"] == sizes["lm_head"] == 12544 * 2048


def test_work_counting_functions_against_hand_counts():
    builder = manifest.load_module("builders", "laguna_adamw")
    assert builder.band_pairs(6) == 21
    assert builder.band_pairs(6, 3) == 6 + 3 * 3    # rows 0-2, then 3 each
    assert builder.band_pairs(6, 9) == 21           # a window past the end
    cell = manifest.Cell(CELL)
    c, seq = cell.config, 8192
    assert builder.layers(c) == [
        ("full_attention", 48, "dense"), ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"), ("full_attention", 48, "sparse")]
    assert builder.sparse_layers(c) == 4
    # T x 8 x 16 / 256: the even share of ISSUE 32.
    assert builder.expected_rows_held(c, 2 * seq) == 8192
    # The band of a sliding layer: 512 x 513 / 2 + 7680 x 512 pairs a
    # sequence, 64 heads over 8, three layers, as flash_band_work counts.
    pairs = builder.band_pairs(seq, 512)
    assert pairs == 131328 + 7680 * 512
    flash = manifest.load_module("layer_metrics", "attn_flash_roofline")
    f, b = flash.flash_band_work(2, 64, 8, seq, 128, pairs, 2)
    assert builder.window_flash_work(c, 2, seq, 2) == (3 * f, 3 * b)
    assert f == (8 + 6 + 8) * 2 * 64 * pairs * 128
    # ISSUE 32's "4.5 TFLOP of band" a step (forward counted twice).
    assert 4.3e12 < 3 * f < 4.5e12
    # The step: 6 x tokens x the matrices met, the held experts' rows,
    # the bands.
    full, sliding, mlp, shared, router, expert = builder.matrix_parameters(c)
    met = 2 * full + 3 * sliding + mlp + 4 * (shared + router) \
        + 2048 * 12544
    attention = 12 * 128 * 2 * (2 * 48 * builder.band_pairs(seq)
                                + 3 * 64 * pairs)
    assert builder.train_flops_per_step(c, 2, seq) == \
        6.0 * 2 * seq * met + 6.0 * 4 * 8192 * expert + attention
    assert 3.8e13 < builder.train_flops_per_step(c, 2, seq) < 3.9e13


def test_starting_weights_scale_what_writes_into_the_residual_stream():
    import jax.numpy as jnp
    import numpy as np

    cell = manifest.Cell(CELL, rehearsal=True)
    builder = manifest.load_module("builders", cell.config["builder"])
    one = {"kernel": jnp.ones((2, 2))}
    draws = {"layer_0": {"attention": {"wo": one, "wq": one, "wg": one},
                         "mlp": {"w_down": one, "w_up": one}},
             "layer_1": {"shared": {"w_down": one, "w_gate": one},
                         "w_down": one, "w_up": one, "router": one}}
    out = builder.starting_weights(cell.config, draws)
    small = (2 * 40) ** -0.5
    for path, want in [
            (("layer_0", "attention", "wo"), small),
            (("layer_0", "attention", "wq"), 1), (("layer_0", "attention", "wg"), 1),
            (("layer_0", "mlp", "w_down"), small), (("layer_0", "mlp", "w_up"), 1),
            (("layer_1", "shared", "w_down"), small),
            (("layer_1", "shared", "w_gate"), 1), (("layer_1", "w_down"), small),
            (("layer_1", "w_up"), 1), (("layer_1", "router"), 1)]:
        leaf = out
        for name in path:
            leaf = leaf[name]
        np.testing.assert_allclose(leaf["kernel"], want, rtol=1e-6)


def test_the_builder_reads_the_rotary_groups_of_the_configuration():
    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    cfg = builder.model_config(cell.config)
    from horovod_tpu.models import LAGUNA_XS2

    assert cfg.full_rotary == LAGUNA_XS2.full_rotary
    assert cfg.sliding_rotary == LAGUNA_XS2.sliding_rotary
    assert (cfg.num_experts, cfg.experts_held, cfg.num_layers,
            cfg.vocab_size) == (256, tuple(range(16)), 5, 12544)
    import dataclasses
    assert dataclasses.replace(
        cfg, experts_held=None, num_layers=40, vocab_size=100352,
        remat=False) == LAGUNA_XS2
    with pytest.raises(ValueError, match="counts the routed experts held"):
        builder.model_config({**cell.config, "num_experts": 256})


# ------------------------------------------------- the readers, by hand

STEP = "jit(train_step)/shard_map/"
BACK = STEP + "transpose(jvp(LagunaLM))/layer_1/"
FULL = STEP + "jvp(LagunaLM)/layer_0/attention/hvd.attn.full/"
WINDOW = BACK + "attention/hvd.attn.window/"
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_rotary (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %r.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{BACK}attention/hvd.attn.pointwise/mul"}}
}}

%fused_wq (p0: f32[8,8]) -> f32[8,8] {{
  %p0.1 = f32[8,8]{{1,0}} parameter(0)
  %r.2 = f32[8,8]{{1,0}} multiply(%p0.1, %p0.1), metadata={{op_name="{BACK}attention/hvd.attn.pointwise/add_any"}}
  ROOT %dot.1 = f32[8,8]{{1,0}} dot(%r.2, %p0.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{BACK}attention/wq/dot_general"}}
}}

%fused_shared (p0: f32[8]) -> f32[8] {{
  %p0.2 = f32[8]{{0}} parameter(0)
  ROOT %s.1 = f32[8]{{0}} multiply(%p0.2, %p0.2), metadata={{op_name="{BACK}hvd.moe.shared/shared/mul"}}
}}

%fused_gather (p0: f32[8]) -> f32[8] {{
  %p0.3 = f32[8]{{0}} parameter(0)
  ROOT %g.1 = f32[8]{{0}} multiply(%p0.3, %p0.3), metadata={{op_name="{BACK}hvd.moe.dispatch/gather"}}
}}

ENTRY %main (a: f32[8], b: f32[8,8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %b = f32[8,8]{{1,0}} parameter(1)
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_rotary
  %fusion.2 = f32[8,8]{{1,0}} fusion(%b), kind=kOutput, calls=%fused_wq
  %fusion.3 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_shared
  %fusion.4 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_gather
  %cast.1 = f32[8]{{0}} convert(%a), metadata={{op_name="{BACK}hvd.moe.experts/convert_element_type"}}
  %fold.1 = f32[8]{{0}} copy(%a), metadata={{op_name="{WINDOW}transpose"}}
  %top.1 = f32[8]{{0}} sort(%fusion.4), metadata={{op_name="{STEP}jvp(LagunaLM)/layer_1/hvd.moe.route/top_k"}}
  %ragged-dot-none = f32[8]{{0}} custom-call(%top.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %flash.1 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{FULL}hvd_flash_fwd/pallas_call"}}
  %flash.2 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{WINDOW}hvd_flash_bwd_dq/pallas_call"}}
  %flash.3 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{WINDOW}hvd_flash_fwd/pallas_call"}}
  ROOT %other = f32[8]{{0}} add(%flash.1, %flash.2), metadata={{op_name="{BACK}add"}}
}}
'''
# One device, two steps; ns. fusion.2 holds a pass of the rotation's
# transpose and wq's product: it counts with the product, not as a
# pointwise pass. fold.1 is under the window's scope and no kernel.
HAND_EVENTS = [("fusion.1", 0, 100), ("fusion.2", 100, 700),
               ("fusion.3", 800, 60), ("fusion.4", 860, 140),
               ("fold.1", 1000, 30), ("top.1", 1030, 50),
               ("ragged-dot-none", 1080, 1000), ("flash.1", 2080, 400),
               ("flash.2", 2480, 600), ("flash.3", 3080, 200),
               ("other", 3280, 50), ("cast.1", 3330, 40)]


def _hand_run():
    trace = Trace({"/device:TPU:0": HAND_EVENTS}, [], {},
                  {"ragged-dot-none", "flash.1", "flash.2", "flash.3"})
    return {"trace": trace, "compiled_text": HAND_TEXT, "steps": 2,
            "cell": manifest.Cell(CELL), "chips": 1,
            "stamp": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "moe_load": [[30, 10, 20, 20], [5, 5, 5, 25]]}


def _read(name, run):
    return manifest.load_module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name,expected", [
    ("attn_window_ms", (600 + 200) / 2 / 1e6),
    ("attn_full_ms", 400 / 2 / 1e6),
    ("attn_pointwise_ms", 100 / 2 / 1e6),
    ("moe_shared_ms", 60 / 2 / 1e6),
    ("moe_held_ms", (140 + 50 + 1000 + 40) / 2 / 1e6),
    ("moe_held_load_max_over_mean", 25 * 4 / 40),
])
def test_readers_on_the_hand_run(name, expected):
    assert _read(name, _hand_run()) == pytest.approx(expected)


def test_rooflines_on_the_hand_run(capsys):
    run = _hand_run()
    experts = manifest.load_module("layer_metrics", "moe_experts_roofline")
    flops, nbytes = experts.experts_work(120, 8, 2048, 512)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("moe_held_experts_roofline", run) == pytest.approx(
        100 * least / (1040 / 2 / 1e9))
    builder = manifest.load_module("builders", "laguna_adamw")
    # The hand text makes one forward call under the window's scope for
    # the configuration's three sliding layers.
    flops, nbytes = builder.window_flash_work(run["cell"].config, 2, 8192,
                                              1 / 3)
    least = max(flops / 197e12, nbytes / 819e9)
    assert flops / 197e12 > nbytes / 819e9
    assert _read("attn_window_roofline", run) == pytest.approx(
        100 * least / (800 / 2 / 1e9))
    said = capsys.readouterr().out
    assert "0.333333 forward calls a layer; bound by flops" in said


def test_readers_return_nothing_on_a_program_without_the_scopes():
    """The parent's programs plant no such scope: every new reader returns
    ``None`` and raises nothing; so does a run with no device trace."""
    run = _hand_run()
    run["compiled_text"] = HAND_TEXT.replace("hvd.attn.", "attn.").replace(
        "hvd.moe.", "moe.")
    del run["moe_load"]
    for name in NEW_METRICS:
        assert _read(name, run) is None
    untraced = dict(_hand_run(), trace=None)
    for name in NEW_METRICS[:-1]:
        assert _read(name, untraced) is None
    # Another configuration's cell names its expert width otherwise: the
    # reader of this one's says nothing there.
    other = dict(_hand_run(), cell=manifest.Cell(
        "smallthinker-21b-a3b-s8k-ep4share"))
    assert _read("moe_held_experts_roofline", other) is None
