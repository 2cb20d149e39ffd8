"""The JoyAI-LLM-Flash cell (PR 40) rehearsed on the CPU, and the readers
and work-counting functions it brought, on hand counts and a hand-made
run.

``run.py --rehearse-cpu`` end to end in a child process, traced, at the
tiny sizes the configuration and traffic files give (two sequences of
1024 so that the flash kernels stream with q and k 48 wide over v 32, the
dense layer, two sparse ones with 3 of 8 experts held under the sigmoid
rule with its bias beside a shared expert, and the multi-token-prediction
module through the main head; a vocabulary slice of 512). The broken
steps and the int8 control are ``test_control_joyai.py``."""

import json
import os
import re
import subprocess
import sys

import pytest

from harness import manifest
from harness.trace_reduce import Trace

CELL = "joyai-llm-flash-s8k-ep32share"
RUN = os.path.join(manifest.BENCH_DIR, "run.py")
NEW_METRICS = ("attn_latent_flash_ms", "attn_latent_flash_roofline",
               "attn_latent_proj_ms", "mtp_ms", "moe_sigmoid256_held_ms",
               "moe_sigmoid256_experts_roofline",
               "moe_sigmoid256_load_max_over_mean")
LAYER_OF = {**dict.fromkeys(NEW_METRICS[:2], "kernels: ops/attention.py"),
            **dict.fromkeys(NEW_METRICS[2:4],
                            "models: models/resnet.py, models/bert.py"),
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
    assert len(checks) >= 10 and all(ln.endswith(" ok") for ln in checks)
    # The program counted what landed on the held experts of the two
    # sparse layers and of the module's block: 2 x 1024 tokens x 2 chosen
    # x 3 of 8 held. Three experts of eight and the bias make the toy's
    # spread wider than the cell's.
    line = next(ln for ln in lines if ln.startswith("[moe] "))
    landed = json.loads(re.search(r"last\): (\[[\d, ]+\])", line).group(1))
    assert len(landed) == 3 and "(expected 1536 a block)" in line
    assert all(0.6 * 1536 < rows < 1.4 * 1536 for rows in landed)


def test_the_cell_lists_the_new_metrics_and_no_other_cell_does():
    m = manifest.load_manifest()
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == LAYER_OF[name]
        assert by_name[name]["moves"] == "train_samples_per_s_per_chip"
    assert [p["name"] for p in m["per_layer"][-7:]] == list(NEW_METRICS)
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and set(NEW_METRICS) <= {
        p["name"] for p in cell.per_layer}
    assert m["workloads"][-1] == {
        "name": CELL, "config": "joyai-llm-flash",
        "traffic": "causal-s8192-b2-dp1", "chips": 1,
        "why": m["workloads"][-1]["why"]}
    # Every per-layer metric with no list of its own applies here too,
    # and the accepted lists were left as they were.
    assert {p["name"] for p in m["per_layer"] if "workloads" not in p} <= {
        p["name"] for p in cell.per_layer}
    for name in ("moe_experts_ms", "attn_flash_ms", "loss_head_ms",
                 "moe_held_ms", "attn_full_ms", "moe_sigmoid_held_ms",
                 "attn_head64_flash_ms"):
        assert CELL not in by_name[name]["workloads"]


def test_the_configuration_is_the_share_it_states():
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "joyai-llm-flash")
    config = manifest.Cell(CELL).config
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/" \
        "config.json"
    assert entry["reduced"] == config["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    deployment = config["deployment"]
    assert deployment["experts_held"] == list(range(8))
    assert deployment["chips_sharing_a_layer"] == 32
    assert config["n_routed_experts"] == 8 and deployment["router_width"] \
        == config["published"]["n_routed_experts"] == 256
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] \
        == 129280
    assert (config["num_layers"], config["num_hidden_layers"],
            config["published"]["num_hidden_layers"]) == (5, 40, 40)
    # Every width as published, with the router's outputs, the experts a
    # token, the rotary base, both eps, the scale and the module.
    assert (config["hidden_size"], config["q_lora_rank"],
            config["kv_lora_rank"], config["num_attention_heads"],
            config["num_key_value_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["qk_head_dim"],
            config["v_head_dim"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["n_shared_experts"],
            config["first_k_dense_replace"], config["rope_theta"],
            config["rms_norm_eps"], config["routing_weight_sum_eps"],
            config["routed_scaling_factor"],
            config["num_nextn_predict_layers"], config["mtp_loss_weight"]
            ) == (2048, 1536, 512, 32, 32, 128, 64, 192, 128, 64, 7168, 768,
                  8, 1, 1, 32000000, 1e-06, 1e-20, 2.5, 1, 0.1)
    assert config["rope_interleave"] and config["rope_scaling"] is None \
        and config["norm_topk_prob"] and not config["tie_word_embeddings"]
    assert (config["scoring_func"], config["topk_method"], config["n_group"],
            config["topk_group"]) == ("sigmoid", "noaux_tc", 1, 1)
    for key in ("published", "deployment", "assumed", "rehearsal"):
        assert config[key]
    # The three learning rates' readings are in the file, and the rate
    # chosen is the largest that holds the share.
    readings = config["assumed"]["optimizer_readings"]
    assert {"1e-5", "1e-6", "1e-7"} <= set(readings["rates"])
    chosen = f"{config['optimizer']['learning_rate']:.0e}".replace(
        "e-0", "e-")
    worst = {rate: max(readings["rates"][rate]["worst_over_even"])
             for rate in readings["rates"]}
    assert worst[chosen] <= 1.25
    assert all(worst[rate] > 1.25 for rate in worst
               if float(rate) > float(chosen))
    # The rehearsal keeps every mechanism alive, at the published 3:2 of
    # the two widths.
    toy = manifest.Cell(CELL, rehearsal=True).config
    builder = manifest.load_module("builders", config["builder"])
    assert (builder.attention_blocks(toy), builder.sparse_blocks(toy),
            toy["first_k_dense_replace"], toy["qk_head_dim"],
            toy["v_head_dim"], len(toy["deployment"]["experts_held"]),
            toy["deployment"]["router_width"], toy["num_experts_per_tok"],
            toy["vocab_size"]) == (4, 3, 1, 48, 32, 3, 8, 2, 512)


def test_parameters_add_up_as_the_configuration_says():
    """The builder's tree of shapes against ISSUE 40's arithmetic: latent
    attention 26.35M, the dense FFN 44.04M, one expert 4.719M, the router
    0.52M, the module 77.74M, 491.7M in all."""
    import jax
    import numpy as np

    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    attention, mlp, shared, router, expert, eh_proj = \
        builder.matrix_parameters(cell.config)
    assert attention == (2048 * 1536 + 1536 * 6144 + 2048 * 576
                         + 512 * 8192 + 4096 * 2048)
    assert mlp == 3 * 2048 * 7168 and shared == expert == 3 * 2048 * 768
    assert router == 2048 * 256 and eh_proj == 4096 * 2048
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    bench = builder.build(cell.config, cell.traffic, mesh)
    sizes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(bench.weight_params, bench.weight_shapes))[0]:
        top = str(getattr(path[0], "key", path[0]))
        sizes[top] = sizes.get(top, 0) + int(np.prod(leaf.shape))
    total = sum(sizes[top] for top in sorted(sizes))
    assert total == 491_697_408
    assert "491,697,408" in cell.config["deployment"]["parameters_here"]
    mixer = attention + 1536 + 512          # the two inner norms
    sparse = mixer + 8 * expert + shared + router + 256 + 2 * 2048
    assert sizes["layer_0"] == mixer + mlp + 2 * 2048
    assert all(sizes[f"layer_{i}"] == sparse for i in range(1, 5))
    assert sizes["mtp"] == sparse + eh_proj + 3 * 2048
    assert sizes["tok_embeddings"] == sizes["lm_head"] == 16160 * 2048


def test_work_counting_functions_against_hand_counts():
    builder = manifest.load_module("builders", "joyai_adamw")
    c, seq = manifest.Cell(CELL).config, 8192
    assert (builder.attention_blocks(c), builder.sparse_blocks(c)) == (6, 5)
    # T x 8 x 8 / 256: 512 rows an expert.
    assert builder.expected_rows_held(c, 2 * seq) == 4096
    pairs = builder.band_pairs(seq)
    assert pairs == seq * (seq + 1) // 2
    # A pair: 640 FLOPs a forward call, 1024 in dq, 1280 in dk/dv.
    flops, nbytes = builder.latent_flash_work(c, 2, seq, 2)
    assert flops == 6 * (2 * 640 + 1024 + 1280) * 2 * 32 * pairs
    wide, narrow, stat = (2 * 32 * seq * 192 * 2, 2 * 32 * seq * 128 * 2,
                          2 * 32 * seq * 4)
    assert nbytes == 6 * (2 * (2 * wide + 2 * narrow + stat)
                          + 3 * wide + 2 * narrow + 2 * stat
                          + 3 * wide + 3 * narrow + 2 * stat)
    # FLOPs bound it, ten to one: 234 ms a step at the bf16 peak.
    assert flops / 197e12 > 9 * nbytes / 819e9
    assert 0.23 < flops / 197e12 < 0.24
    # The step: 6 x tokens x the matrices met (two head passes), the held
    # experts' rows in five blocks, 3 x 640 a causal pair in six.
    attention, mlp, shared, router, expert, eh_proj = \
        builder.matrix_parameters(c)
    met = 6 * attention + mlp + 5 * (shared + router) + eh_proj \
        + 2 * 2048 * 16160
    assert builder.train_flops_per_step(c, 2, seq) == \
        6.0 * 2 * seq * met + 6.0 * 5 * 4096 * expert \
        + 3.0 * 640 * 32 * 2 * pairs * 6
    assert 5.5e13 < builder.train_flops_per_step(c, 2, seq) < 5.52e13


def test_the_builder_reads_the_model_from_the_configuration():
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import JOYAI_LLM_FLASH

    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    cfg = builder.model_config(cell.config)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_layers,
            cfg.vocab_size, cfg.qk_head_dim, cfg.mtp_layers) == (
        256, tuple(range(8)), 5, 16160, 192, 1)
    assert dataclasses.replace(
        cfg, experts_held=None, num_layers=40, vocab_size=129280,
        remat=False) == JOYAI_LLM_FLASH
    with pytest.raises(ValueError, match="counts the routed experts held"):
        builder.model_config({**cell.config, "n_routed_experts": 256})
    # What the program's block has no other form of.
    for key, other in (("attention_bias", True), ("rope_interleave", False),
                       ("rope_scaling", {"type": "yarn"}),
                       ("scoring_func", "softmax"), ("n_group", 8),
                       ("norm_topk_prob", False),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="the program's block"):
            builder.model_config({**cell.config, key: other})
    with pytest.raises(ValueError, match="head_dim the rotary part"):
        builder.model_config({**cell.config, "head_dim": 128})
    # The seeded start: the residual's projections small, the bias at its
    # own deviation for the harness's 0.02, the module's projection as
    # drawn.
    one = {"kernel": jnp.ones((2, 2))}
    out = builder.starting_weights(cell.config, {
        "layer_1": {"attention": {"wo": one, "wq_b": one}, "w_down": one,
                    "shared": {"w_down": one, "w_up": one},
                    "expert_bias": {"kernel": jnp.array([0.02, -0.04])}},
        "mtp": {"eh_proj": one, "block": {"w_down": one}}})
    small = (2 * 40) ** -0.5
    for path, want in [(("layer_1", "attention", "wo"), small),
                       (("layer_1", "attention", "wq_b"), 1),
                       (("layer_1", "w_down"), small),
                       (("layer_1", "shared", "w_down"), small),
                       (("layer_1", "shared", "w_up"), 1),
                       (("mtp", "eh_proj"), 1),
                       (("mtp", "block", "w_down"), small)]:
        leaf = out
        for name in path:
            leaf = leaf[name]
        np.testing.assert_allclose(leaf["kernel"], want, rtol=1e-6)
    np.testing.assert_allclose(out["layer_1"]["expert_bias"]["kernel"],
                               [0.005, -0.01], rtol=1e-6)


# ------------------------------------------------- the readers, by hand

STEP = "jit(train_step)/"
BACK = STEP + "transpose(jvp(JoyAILM))/jvp(JoyAILM)/checkpoint/layer_2/"
MIXER = BACK + "attention/"
MTP = STEP + "jvp(JoyAILM)/hvd.mtp/mtp/"
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_rotate (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %r.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{MIXER}hvd.attn.latent.proj/mul"}}
}}

%fused_route (p0: f32[8]) -> f32[8] {{
  %p0.2 = f32[8]{{0}} parameter(0)
  ROOT %s.1 = f32[8]{{0}} logistic(%p0.2), metadata={{op_name="{BACK}hvd.moe.route/logistic"}}
}}

%fused_module (p0: f32[8]) -> f32[8] {{
  %p0.3 = f32[8]{{0}} parameter(0)
  ROOT %g.1 = f32[8]{{0}} multiply(%p0.3, %p0.3), metadata={{op_name="{MTP}block/hvd.moe.dispatch/gather"}}
}}

ENTRY %main (a: f32[8], b: f32[8,8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %b = f32[8,8]{{1,0}} parameter(1)
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_rotate
  %fusion.3 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_route
  %fusion.4 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_module
  %up.1 = f32[8,8]{{1,0}} dot(%b, %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{MIXER}hvd.attn.latent.proj/wq_b/dot_general"}}
  %wo.1 = f32[8,8]{{1,0}} dot(%b, %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{MIXER}wo/dot_general"}}
  %eh.1 = f32[8,8]{{1,0}} dot(%b, %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{MTP}eh_proj/dot_general"}}
  %cast.1 = f32[8]{{0}} convert(%a), metadata={{op_name="{BACK}hvd.moe.experts/convert_element_type"}}
  %fold.1 = f32[8]{{0}} copy(%a), metadata={{op_name="{MIXER}hvd.attn.latent/transpose"}}
  %ragged-dot-none = f32[8]{{0}} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %flash.1 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{MIXER}hvd.attn.latent/hvd_flash_fwd/pallas_call"}}
  %flash.2 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{MIXER}hvd.attn.latent/hvd_flash_bwd_dq/pallas_call"}}
  %flash.3 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{MTP}block/attention/hvd.attn.latent/hvd_flash_fwd/pallas_call"}}
  %flash.4 = f32[8]{{0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}jvp(JoyAILM)/layer_1/attention/hvd_flash_fwd/pallas_call"}}
  %head.2 = f32[8]{{0}} while(%a), condition=%c, body=%d, metadata={{op_name="{STEP}jvp(JoyAILM)/hvd.mtp/hvd.loss.head/while"}}
  ROOT %other = f32[8]{{0}} add(%flash.1, %flash.2), metadata={{op_name="{BACK}add"}}
}}
'''
# One device, two steps; ns. fold.1 is under attention's scope and no
# kernel; flash.4 a kernel under no scope; wo.1 the mixer's and under
# neither of its scopes; flash.3, fusion.4, eh.1 and head.2 the module's.
HAND_EVENTS = [("fusion.1", 0, 100), ("up.1", 100, 700),
               ("fusion.3", 800, 60), ("fusion.4", 860, 140),
               ("wo.1", 1000, 900), ("fold.1", 1900, 30),
               ("ragged-dot-none", 1930, 1000), ("flash.1", 2930, 400),
               ("flash.2", 3330, 600), ("flash.3", 3930, 200),
               ("flash.4", 4130, 250), ("eh.1", 4380, 120),
               ("head.2", 4500, 300), ("other", 4800, 50),
               ("cast.1", 4850, 40)]


def _hand_run():
    trace = Trace({"/device:TPU:0": HAND_EVENTS}, [], {},
                  {"ragged-dot-none", "flash.1", "flash.2", "flash.3",
                   "flash.4"})
    return {"trace": trace, "compiled_text": HAND_TEXT, "steps": 2,
            "cell": manifest.Cell(CELL), "chips": 1,
            "stamp": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "moe_load": [[30, 10, 20, 20], [5, 5, 5, 25]]}


def _read(name, run):
    return manifest.load_module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name,expected", [
    ("attn_latent_flash_ms", (400 + 600 + 200) / 2 / 1e6),
    ("attn_latent_proj_ms", (100 + 700) / 2 / 1e6),
    ("mtp_ms", (140 + 200 + 120 + 300) / 2 / 1e6),
    ("moe_sigmoid256_held_ms", (60 + 140 + 1000 + 40) / 2 / 1e6),
    ("moe_sigmoid256_load_max_over_mean", 25 * 4 / 40),
])
def test_readers_on_the_hand_run(name, expected):
    assert _read(name, _hand_run()) == pytest.approx(expected)


def test_rooflines_on_the_hand_run(capsys):
    run = _hand_run()
    experts = manifest.load_module("layer_metrics", "moe_experts_roofline")
    flops, nbytes = experts.experts_work(120, 8, 2048, 768)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("moe_sigmoid256_experts_roofline", run) == pytest.approx(
        100 * least / (1040 / 2 / 1e9))
    builder = manifest.load_module("builders", "joyai_adamw")
    # The hand text makes two forward calls under the scope for the
    # configuration's six attending blocks: a third of a call a block.
    flops, nbytes = builder.latent_flash_work(run["cell"].config, 2, 8192,
                                              2 / 6)
    assert flops / 197e12 > nbytes / 819e9
    assert _read("attn_latent_flash_roofline", run) == pytest.approx(
        100 * (flops / 197e12) / (1200 / 2 / 1e9))
    said = capsys.readouterr().out
    assert "0.333333 forward calls a block; bound by flops" in said


def test_readers_return_nothing_on_a_program_without_the_scopes():
    """The parent's programs plant no such scope: every new reader returns
    ``None`` and raises nothing; so does a run with no device trace."""
    run = _hand_run()
    run["compiled_text"] = HAND_TEXT.replace("hvd.attn.", "attn.").replace(
        "hvd.mtp", "mtp").replace("hvd.moe.", "moe.")
    del run["moe_load"]
    for name in NEW_METRICS:
        assert _read(name, run) is None
    untraced = dict(_hand_run(), trace=None)
    for name in NEW_METRICS[:-1]:
        assert _read(name, untraced) is None
