"""The LFM2 cell (PR 38) rehearsed on the CPU, and the readers and
work-counting functions it brought, on hand counts and a hand-made run.

``run.py --rehearse-cpu`` end to end in a child process, traced, at the
tiny sizes the configuration and traffic files give (two sequences of
1024 so that the flash kernels stream at head width 32, both mixers, the
dense layer, 3 of 8 experts held, 2 chosen under the sigmoid rule with
its bias, a vocabulary slice of 512 through the tied head). The broken
steps and the int8 control are ``test_control_lfm2.py``."""

import json
import os
import re
import subprocess
import sys

import pytest

from harness import manifest
from harness.trace_reduce import Trace

CELL = "lfm2-24b-a2b-s8k-ep8share"
RUN = os.path.join(manifest.BENCH_DIR, "run.py")
NEW_METRICS = ("shortconv_ms", "shortconv_pointwise_ms",
               "shortconv_pointwise_roofline", "attn_head64_flash_ms",
               "attn_head64_flash_roofline", "moe_sigmoid_route_ms",
               "moe_sigmoid_held_ms", "moe_sigmoid_experts_roofline",
               "moe_sigmoid_load_max_over_mean")
LAYER_OF = {"shortconv_ms": "models: models/resnet.py, models/bert.py",
            **dict.fromkeys(NEW_METRICS[1:3],
                            "linear attention: ops/linear_attention.py"),
            **dict.fromkeys(NEW_METRICS[3:5], "kernels: ops/attention.py"),
            **dict.fromkeys(NEW_METRICS[5:],
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
    # sparse layers: 2 x 1024 tokens x 2 chosen x 3 of 8 held. Three
    # experts of eight and the bias make the toy's spread wider than the
    # cell's.
    line = next(ln for ln in lines if ln.startswith("[moe] "))
    landed = json.loads(re.search(r"layer: (\[[\d, ]+\])", line).group(1))
    assert len(landed) == 4 and "(expected 1536 a layer)" in line
    assert all(0.7 * 1536 < rows < 1.35 * 1536 for rows in landed)


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
                 "attn_flash_roofline", "loss_head_ms", "moe_held_ms",
                 "attn_full_ms", "linattn_pointwise_ms"):
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_is_the_share_it_states():
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "lfm2-24b-a2b")
    config = manifest.Cell(CELL).config
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert entry["reduced"] == config["reduced"] == [
        "num_layers", "num_dense_layers", "num_experts", "vocab_size"]
    deployment = config["deployment"]
    assert deployment["experts_held"] == list(range(8))
    assert deployment["chips_sharing_a_layer"] == 8
    assert config["num_experts"] == 8 and deployment["router_width"] == \
        config["published"]["num_experts"] == 64
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert (config["num_dense_layers"],
            config["published"]["num_dense_layers"]) == (1, 2)
    # Published layer 0 and the four that follow the dense ones.
    assert deployment["layers_run"] == [0, 2, 3, 4, 5]
    assert len(config["layer_types"]) == config["num_hidden_layers"] \
        == config["published"]["num_hidden_layers"] == 40
    assert config["layer_types"] == [
        "conv", "conv", "full_attention", "conv"] * 10
    builder = manifest.load_module("builders", config["builder"])
    assert builder.layers(config) == [
        ("conv", False), ("full_attention", True), ("conv", True),
        ("conv", True), ("conv", True)]
    # Every width, the router's outputs, the experts a token, the taps,
    # the head's width and the rotary base as published.
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["conv_L_cache"], builder.head_dim(config),
            config["rope_parameters"]["rope_theta"], config["norm_eps"],
            config["routed_scaling_factor"]) == (
        2048, 11776, 1536, 4, 32, 8, 3, 64, 1000000, 1e-05, 1)
    assert config["use_expert_bias"] and config["norm_topk_prob"] \
        and not config["conv_bias"]
    for key in ("published", "deployment", "assumed", "rehearsal"):
        assert config[key]
    # The issue's three learning rates' readings are in the file, and
    # the next decade's, which is the rate chosen: at none of the three
    # do the landed rows stay within 1.25 times the even share through
    # twice a run's steps.
    readings = config["assumed"]["optimizer_readings"]
    assert set(readings["rates"]) == {"1e-4", "1e-5", "1e-6", "1e-7"}
    assert config["optimizer"]["learning_rate"] == 1e-7
    worst = {rate: max(readings["rates"][rate]["worst_over_even"])
             for rate in sorted(readings["rates"])}
    assert worst["1e-7"] <= 1.25 < min(
        worst[rate] for rate in ("1e-4", "1e-5", "1e-6"))
    # The rehearsal keeps every mechanism alive.
    toy = manifest.Cell(CELL, rehearsal=True).config
    assert {kind for kind, _ in builder.layers(toy)} == {
        "conv", "full_attention"}
    assert [sparse for _, sparse in builder.layers(toy)] == [
        False, True, True, True, True]
    assert (len(toy["deployment"]["experts_held"]),
            toy["deployment"]["router_width"], toy["num_experts_per_tok"],
            builder.head_dim(toy), toy["vocab_size"]) == (3, 8, 2, 32, 512)


def test_parameters_add_up_as_the_configuration_says():
    """The builder's tree of shapes against ISSUE 38's arithmetic: a
    convolution mixer 16.78M, attention 10.49M, the dense FFN 72.35M, one
    expert 9.437M, the router 0.13M, 469.3M in all with the head tied."""
    import jax
    import numpy as np

    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    conv, attention, mlp, router, expert = \
        builder.matrix_parameters(cell.config)
    assert conv == 2048 * 6144 + 2048 * 2048
    assert attention == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert mlp == 3 * 2048 * 11776 and expert == 3 * 2048 * 1536
    assert router == 2048 * 64
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    bench = builder.build(cell.config, cell.traffic, mesh)
    sizes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(bench.weight_params, bench.weight_shapes))[0]:
        top = str(getattr(path[0], "key", path[0]))
        sizes[top] = sizes.get(top, 0) + int(np.prod(leaf.shape))
    total = sum(sizes[top] for top in sorted(sizes))
    assert total == 469_285_248
    assert "469,285,248" in cell.config["deployment"]["parameters_here"]
    norms, taps, bias = 2 * 2048, 3 * 2048, 64
    assert sizes["layer_0"] == conv + taps + mlp + norms
    assert sizes["layer_1"] == attention + 2 * 64 + 8 * expert + router \
        + bias + norms
    assert sizes["layer_2"] == sizes["layer_3"] == sizes["layer_4"] == \
        conv + taps + 8 * expert + router + bias + norms
    assert sizes["tok_embeddings"] == 8192 * 2048 and "lm_head" not in sizes


def test_work_counting_functions_against_hand_counts():
    builder = manifest.load_module("builders", "lfm2_adamw")
    cell = manifest.Cell(CELL)
    c, seq = cell.config, 8192
    assert builder.sparse_layers(c) == 4
    # T x 4 x 8 / 64: half a token's worth of rows a token, 2,048 an
    # expert.
    assert builder.expected_rows_held(c, 4 * seq) == 16384
    # The gated convolutions: four layers, 4 + 7 arrays of tokens x 2048
    # in bf16; 7.21 ms at 819 GB/s.
    flops, nbytes = builder.shortconv_pointwise_work(c, 4, seq)
    assert nbytes == 4 * 11 * 4 * seq * 2048 * 2
    assert flops / 197e12 < nbytes / 819e9
    assert 7.2e-3 < nbytes / 819e9 < 7.3e-3
    # The one attention layer: every causal pair at 32 heads over 8 of
    # width 64, as flash_band_work counts.
    pairs = builder.band_pairs(seq)
    flash = manifest.load_module("layer_metrics", "attn_flash_roofline")
    f, b = flash.flash_band_work(4, 32, 8, seq, 64, pairs, 2)
    assert builder.head64_flash_work(c, 4, seq, 2) == (f, b)
    assert f == (8 + 6 + 8) * 4 * 32 * pairs * 64
    # The step: 6 x tokens x the matrices met, the held experts' rows,
    # the causal pairs.
    conv, attention, mlp, router, expert = builder.matrix_parameters(c)
    met = 4 * conv + attention + mlp + 4 * router + 2048 * 8192
    assert builder.train_flops_per_step(c, 4, seq) == \
        6.0 * 4 * seq * met + 6.0 * 4 * 16384 * expert \
        + 12.0 * 64 * 32 * 4 * pairs
    assert 3.9e13 < builder.train_flops_per_step(c, 4, seq) < 4.0e13


def test_starting_weights_scale_and_draw_what_the_configuration_says():
    import jax.numpy as jnp
    import numpy as np

    cell = manifest.Cell(CELL, rehearsal=True)
    builder = manifest.load_module("builders", cell.config["builder"])
    one = {"kernel": jnp.ones((2, 2))}
    draws = {"layer_0": {"conv": {"in_proj": one, "out_proj": one,
                                  "taps": {"kernel": jnp.array(
                                      [[-0.02, 0.0], [0.02, 9.0],
                                       [0.0, -9.0]])}},
                         "mlp": {"w_down": one, "w_up": one}},
             "layer_1": {"attention": {"wo": one, "wq": one},
                         "w_down": one, "w_up": one, "router": one,
                         "expert_bias": {"kernel": jnp.array([0.02, -0.04])}},
             "tok_embeddings": {"embedding": jnp.ones((2, 2))}}
    out = builder.starting_weights(cell.config, draws)
    small = (2 * 40) ** -0.5
    for path, want in [
            (("layer_0", "conv", "in_proj"), 1),
            (("layer_0", "conv", "out_proj"), small),
            (("layer_0", "mlp", "w_down"), small),
            (("layer_0", "mlp", "w_up"), 1),
            (("layer_1", "attention", "wo"), small),
            (("layer_1", "attention", "wq"), 1),
            (("layer_1", "w_down"), small), (("layer_1", "w_up"), 1),
            (("layer_1", "router"), 1)]:
        leaf = out
        for name in path:
            leaf = leaf[name]
        np.testing.assert_allclose(leaf["kernel"], want, rtol=1e-6)
    np.testing.assert_allclose(out["tok_embeddings"]["embedding"], 1)
    # A tap: the draw's place in its normal distribution, spread over
    # +-1/sqrt(3): one standard deviation below the mean is 2 x 0.1587 -
    # 1 of the bound.
    taps = np.asarray(out["layer_0"]["conv"]["taps"]["kernel"])
    bound = 3 ** -0.5
    np.testing.assert_allclose(
        taps, [[(2 * 0.158655 - 1) * bound, 0.0],
               [(2 * 0.841345 - 1) * bound, bound], [0.0, -bound]],
        atol=1e-5)
    # The bias at the configuration's 0.005 for the harness's 0.02.
    np.testing.assert_allclose(out["layer_1"]["expert_bias"]["kernel"],
                               [0.005, -0.01], rtol=1e-6)


def test_the_builder_reads_the_model_from_the_configuration():
    import dataclasses

    from horovod_tpu.models import LFM2_24B_A2B

    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    cfg = builder.model_config(cell.config)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_layers,
            cfg.vocab_size, cfg.num_dense_layers, cfg.layer_types) == (
        64, tuple(range(8)), 5, 8192, 1,
        ("conv", "full_attention", "conv", "conv", "conv"))
    assert dataclasses.replace(
        cfg, experts_held=None, num_layers=40, vocab_size=65536,
        num_dense_layers=2, layer_types=LFM2_24B_A2B.layer_types,
        remat=False) == LFM2_24B_A2B
    with pytest.raises(ValueError, match="counts the routed experts held"):
        builder.model_config({**cell.config, "num_experts": 64})
    # What the program's block has no other form of.
    for key, other in (("use_expert_bias", False), ("norm_topk_prob", False),
                       ("tie_embedding", False), ("conv_bias", True),
                       ("routed_scaling_factor", 2.5)):
        with pytest.raises(ValueError, match="the program's block"):
            builder.model_config({**cell.config, key: other})


# ------------------------------------------------- the readers, by hand

STEP = "jit(train_step)/shard_map/"
BACK = STEP + "transpose(jvp(Lfm2LM))/layer_2/"
CONV = BACK + "conv/hvd.shortconv/"
POINT = CONV + "hvd.shortconv.pointwise/"
FULL = STEP + "jvp(Lfm2LM)/layer_1/attention/hvd.attn.full/"
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_gates (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %r.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{POINT}mul"}}
}}

%fused_out_proj (p0: f32[8,8]) -> f32[8,8] {{
  %p0.1 = f32[8,8]{{1,0}} parameter(0)
  %r.2 = f32[8,8]{{1,0}} multiply(%p0.1, %p0.1), metadata={{op_name="{POINT}mul"}}
  ROOT %dot.1 = f32[8,8]{{1,0}} dot(%r.2, %p0.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{CONV}out_proj/dot_general"}}
}}

%fused_route (p0: f32[8]) -> f32[8] {{
  %p0.2 = f32[8]{{0}} parameter(0)
  ROOT %s.1 = f32[8]{{0}} logistic(%p0.2), metadata={{op_name="{BACK}hvd.moe.route/logistic"}}
}}

%fused_gather (p0: f32[8]) -> f32[8] {{
  %p0.3 = f32[8]{{0}} parameter(0)
  ROOT %g.1 = f32[8]{{0}} multiply(%p0.3, %p0.3), metadata={{op_name="{BACK}hvd.moe.dispatch/gather"}}
}}

ENTRY %main (a: f32[8], b: f32[8,8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %b = f32[8,8]{{1,0}} parameter(1)
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_gates
  %fusion.2 = f32[8,8]{{1,0}} fusion(%b), kind=kOutput, calls=%fused_out_proj
  %fusion.3 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_route
  %fusion.4 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_gather
  %in.1 = f32[8,8]{{1,0}} dot(%b, %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{CONV}in_proj/dot_general"}}
  %cast.1 = f32[8]{{0}} convert(%a), metadata={{op_name="{BACK}hvd.moe.experts/convert_element_type"}}
  %fold.1 = f32[8]{{0}} copy(%a), metadata={{op_name="{FULL}transpose"}}
  %ragged-dot-none = f32[8]{{0}} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %flash.1 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{FULL}hvd_flash_fwd/pallas_call"}}
  %flash.2 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{FULL}hvd_flash_bwd_dq/pallas_call"}}
  %flash.3 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}jvp(Lfm2LM)/layer_1/attention/hvd_flash_fwd/pallas_call"}}
  ROOT %other = f32[8]{{0}} add(%flash.1, %flash.2), metadata={{op_name="{BACK}add"}}
}}
'''
# One device, two steps; ns. fusion.2 holds a gate and the out-projection's
# product: it counts with the mixer and not as a pointwise pass. fold.1 is
# under attention's scope and no kernel; flash.3 a kernel under no scope.
HAND_EVENTS = [("fusion.1", 0, 100), ("fusion.2", 100, 700),
               ("fusion.3", 800, 60), ("fusion.4", 860, 140),
               ("in.1", 1000, 900), ("fold.1", 1900, 30),
               ("ragged-dot-none", 1930, 1000), ("flash.1", 2930, 400),
               ("flash.2", 3330, 600), ("flash.3", 3930, 200),
               ("other", 4130, 50), ("cast.1", 4180, 40)]


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
    ("shortconv_ms", (100 + 700 + 900) / 2 / 1e6),
    ("shortconv_pointwise_ms", 100 / 2 / 1e6),
    ("attn_head64_flash_ms", (400 + 600) / 2 / 1e6),
    ("moe_sigmoid_route_ms", 60 / 2 / 1e6),
    ("moe_sigmoid_held_ms", (60 + 140 + 1000 + 40) / 2 / 1e6),
    ("moe_sigmoid_load_max_over_mean", 25 * 4 / 40),
])
def test_readers_on_the_hand_run(name, expected):
    assert _read(name, _hand_run()) == pytest.approx(expected)


def test_rooflines_on_the_hand_run(capsys):
    run = _hand_run()
    experts = manifest.load_module("layer_metrics", "moe_experts_roofline")
    flops, nbytes = experts.experts_work(120, 8, 2048, 1536)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("moe_sigmoid_experts_roofline", run) == pytest.approx(
        100 * least / (1040 / 2 / 1e9))
    builder = manifest.load_module("builders", "lfm2_adamw")
    config = run["cell"].config
    # The hand text makes one forward call under attention's scope for
    # the configuration's one attention layer.
    flops, nbytes = builder.head64_flash_work(config, 4, 8192, 1)
    least = max(flops / 197e12, nbytes / 819e9)
    assert flops / 197e12 > nbytes / 819e9
    assert _read("attn_head64_flash_roofline", run) == pytest.approx(
        100 * least / (1000 / 2 / 1e9))
    flops, nbytes = builder.shortconv_pointwise_work(config, 4, 8192)
    assert _read("shortconv_pointwise_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / (100 / 2 / 1e9))
    said = capsys.readouterr().out
    assert "1 forward calls a layer; bound by flops" in said
    assert "[shortconv_pointwise_roofline] bound by bytes" in said


def test_readers_return_nothing_on_a_program_without_the_scopes():
    """The parent's programs plant no such scope: every new reader returns
    ``None`` and raises nothing; so does a run with no device trace."""
    run = _hand_run()
    run["compiled_text"] = HAND_TEXT.replace(
        "hvd.shortconv", "shortconv").replace("hvd.attn.", "attn.").replace(
        "hvd.moe.", "moe.")
    del run["moe_load"]
    for name in NEW_METRICS:
        assert _read(name, run) is None
    untraced = dict(_hand_run(), trace=None)
    for name in NEW_METRICS[:-1]:
        assert _read(name, untraced) is None
