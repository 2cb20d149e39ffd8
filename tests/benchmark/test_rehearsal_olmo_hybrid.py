"""The Olmo-Hybrid cell (PR 30) rehearsed on the CPU, and the readers and
work-counting functions it brought, on hand counts and a hand-made run.

``run.py --rehearse-cpu`` end to end in a child process, traced, at the
tiny sizes the configuration and traffic files give (one sequence of 256,
4 layers, 2 of 4 heads held, widths 16 / 32: four chunks of 64). The controls
and the broken steps are ``test_control_olmo_hybrid.py``."""

import json
import os
import re
import subprocess
import sys

import pytest

from harness import manifest
from harness.trace_reduce import Trace

CELL = "olmo-hybrid-7b-s8k-tp2share"
RUN = os.path.join(manifest.BENCH_DIR, "run.py")
NEW_METRICS = ("linattn_scan_ms", "linattn_scan_roofline",
               "linattn_pointwise_ms")


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
    # The program's own numbers a linear layer: decays strictly inside
    # (0, 1), a state that is there and bounded.
    line = next(ln for ln in lines if ln.startswith("[linattn] "))
    lowest, mean, norms = (
        part.split(", ") for part in re.split(
            "smallest decay |; mean decay a token |"
            "; largest state norm after the last token ", line)[1:])
    assert len(lowest) == len(mean) == len(norms) == 3
    assert all(x.startswith("e^-") and float(x[2:]) < 0.0 for x in lowest)
    assert all(0.0 < float(x) < 1.0 for x in mean)
    assert all(0.0 < float(x) < 1e3 for x in norms)


def test_the_cell_lists_the_new_metrics_and_no_other_cell_does():
    m = manifest.load_manifest()
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == \
            "linear attention: ops/linear_attention.py"
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and set(NEW_METRICS) <= {
        p["name"] for p in cell.per_layer}
    # Every per-layer metric with no list of its own applies here too.
    assert {p["name"] for p in m["per_layer"] if "workloads" not in p} <= {
        p["name"] for p in cell.per_layer}


def test_the_configuration_is_the_share_it_states():
    config = manifest.Cell(CELL).config
    assert config["reduced"] == [
        "num_layers", "num_attention_heads", "num_key_value_heads",
        "linear_num_key_heads", "linear_num_value_heads", "vocab_size"]
    held = config["deployment"]["heads_held"]
    assert held == list(range(15))
    assert config["deployment"]["chips_sharing_a_layer"] == 2
    for key in config["reduced"][1:5]:
        assert config[key] == len(held) == config["published"][key] // 2
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["layer_types"][:config["num_layers"]] == \
        ["linear_attention"] * 3 + ["full_attention"]
    assert len(config["layer_types"]) == config["num_hidden_layers"] \
        == config["published"]["num_hidden_layers"] == 32
    # Every width as published.
    assert (config["hidden_size"], config["intermediate_size"],
            config["head_dim"], config["linear_key_head_dim"],
            config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"]) == (3840, 11008, 128, 96,
                                                  192, 4)


def test_parameters_add_up_as_the_configuration_says():
    """The builder's tree of shapes against ISSUE 30's arithmetic: a
    linear mixer 44.4M, a full one 29.5M, an MLP 126.8M, 766.2M in all."""
    import jax
    import numpy as np

    cell = manifest.Cell(CELL)
    builder = manifest.load_module("builders", cell.config["builder"])
    linear, full, mlp = builder.matrix_parameters(cell.config)
    assert linear == 3840 * (1440 + 1440 + 2880 + 2880 + 15 + 15) \
        + 2880 * 3840
    assert full == 4 * 3840 * 1920 and mlp == 3 * 3840 * 11008
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    bench = builder.build(cell.config, cell.traffic, mesh)
    sizes = [(name, int(np.prod(leaf.shape))) for name, leaf in _named_leaves(
        jax.eval_shape(bench.weight_params, bench.weight_shapes))]

    def under(prefix):
        return sum(n for name, n in sizes if name.startswith(prefix))

    assert round(under("") / 1e6, 1) == 766.2
    assert under("layer_0/mixer/") == linear + 5760 * 4 + 15 + 15 + 192
    assert under("layer_3/mixer/") == full + 2 * 1920


def test_first_gradient_hands_large_matrices_over_by_their_norm():
    """The harness keeps the first gradient beside the state and reads
    its leaf norms alone: a matrix of ``REDUCED_FROM`` elements or more
    arrives as the norms of its columns, whose norm is the matrix's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cell = manifest.Cell(CELL, rehearsal=True)
    builder = manifest.load_module("builders", cell.config["builder"])
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    bench = builder.build(cell.config, cell.traffic, mesh)
    assert builder.REDUCED_FROM == 1024 * 1024
    mu = {"big": jax.random.normal(jax.random.PRNGKey(0), (1024, 1024)),
          "small": jax.random.normal(jax.random.PRNGKey(1), (64, 64)),
          "vector": jnp.arange(8.0)}
    adam = type("Adam", (), {"mu": mu})()
    first = bench.first_gradient((None, (adam,), None))
    assert first["big"].shape == (1024,)
    np.testing.assert_allclose(jnp.linalg.norm(first["big"]),
                               10.0 * jnp.linalg.norm(mu["big"]), rtol=1e-6)
    np.testing.assert_allclose(first["small"], 10.0 * mu["small"], rtol=1e-6)
    np.testing.assert_allclose(first["vector"], 10.0 * mu["vector"],
                               rtol=1e-6)


def test_every_checked_loss_has_a_limit_at_both_sizes():
    """A number that sound runs and the control read apart is judged: the
    three losses, the first gradient and the parameters' change, under the
    same names at the cell's sizes and at the rehearsal's."""
    cell = manifest.Cell(CELL)
    reference = manifest.load_module("reference", cell.config["reference"])
    losses = {f"loss_step{i + 1}"
              for i in range(cell.traffic["checked_steps"])}
    assert len(losses) == 3 and losses <= set(reference.LIMITS)
    assert set(reference.LIMITS) == set(reference.REHEARSAL_LIMITS)
    assert {name[:name.index("_", 6)] for name in set(reference.LIMITS)
            - losses} == {"first_gradient", "param_change"}


def test_a_limit_on_a_difference_is_refused_where_the_gradient_is_reduced(
        monkeypatch, capsys):
    """Column norms carry the norm of a leaf and not the norm of two
    leaves' difference: the builder says so where it reduces (the cell's
    sizes, not the rehearsal's) and refuses a limit that names one."""
    import jax
    import numpy as np

    builder = manifest.load_module("builders", "olmo_hybrid_adamw")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    cell, small = manifest.Cell(CELL), manifest.Cell(CELL, rehearsal=True)
    builder.build(small.config, small.traffic, mesh)
    assert "first_gradient" not in capsys.readouterr().out
    builder.build(cell.config, cell.traffic, mesh)
    said = capsys.readouterr().out
    # 3 x 5 in the linear mixers, 4 in the full one, 4 x 3 MLPs, 2 tables.
    assert "[check] first_gradient: 33 matrices" in said
    assert "first_gradient_difference numbers are void" in said
    named = type("Reference", (), {
        "LIMITS": {"loss_step1": 1.0, "first_gradient_difference": 1.0}})
    monkeypatch.setattr(builder.manifest, "load_module", lambda *_: named)
    builder.build(small.config, small.traffic, mesh)
    with pytest.raises(ValueError, match="first_gradient_difference"):
        builder.build(cell.config, cell.traffic, mesh)


def _named_leaves(tree):
    import jax

    return [("/".join(str(getattr(k, "key", k)) for k in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_starting_weights_draw_what_the_configuration_says():
    """``A_log``, ``dt_bias`` and the taps from the harness's normal draw:
    a decay rate in [1, 16], a step in [0.001, 0.1], taps in +-1/2; ``wo``
    and ``w_down`` scaled by 1/8."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cell = manifest.Cell(CELL, rehearsal=True)
    builder = manifest.load_module("builders", cell.config["builder"])
    key = jax.random.PRNGKey(5)
    draws = {"layer_0": {
        "mixer": {name: {"kernel": 0.02 * jax.random.normal(
            jax.random.fold_in(key, i), shape)}
            for i, (name, shape) in enumerate([
                ("A_log", (4000,)), ("dt_bias", (4000,)),
                ("conv_q", (4, 1000)), ("wo", (8, 8)), ("wq", (8, 8))])},
        "w_down": {"kernel": jnp.ones((4, 4))}}}
    assert builder.draw_shapes({"mixer": {"A_log": 1, "wq": {"kernel": 2}}}) \
        == {"mixer": {"A_log": {"kernel": 1}, "wq": {"kernel": 2}}}
    out = builder.starting_weights(cell.config, draws)["layer_0"]
    rate = np.exp(np.asarray(out["mixer"]["A_log"]))
    assert 1.0 <= rate.min() < 1.2 and 15.8 < rate.max() <= 16.0
    assert abs(rate.mean() - 8.5) < 0.3                 # uniform
    step = np.log1p(np.exp(np.asarray(out["mixer"]["dt_bias"])))
    assert 0.001 <= step.min() < 0.0012 and 0.09 < step.max() <= 0.1
    assert abs(np.log(step).mean() - np.log(0.01)) < 0.1   # log-uniform
    taps = np.asarray(out["mixer"]["conv_q"]["kernel"])
    assert -0.5 <= taps.min() < -0.49 and 0.49 < taps.max() <= 0.5
    np.testing.assert_allclose(out["w_down"]["kernel"], 1 / 8)
    np.testing.assert_allclose(
        out["mixer"]["wo"]["kernel"],
        draws["layer_0"]["mixer"]["wo"]["kernel"] / 8)
    np.testing.assert_array_equal(out["mixer"]["wq"]["kernel"],
                                  draws["layer_0"]["mixer"]["wq"]["kernel"])


def test_work_counting_functions_against_hand_counts():
    builder = manifest.load_module("builders", "olmo_hybrid_adamw")
    # One chunk of 4 tokens, one head, d_k 2, d_v 3, multiply-adds: q k^T
    # and k k^T 4*4*2 each, the solve 4*4*(2+3), the two 4x4 by 4x3
    # products 4*4*3 each, three 2x3 products a token.
    macs = 2 * 32 + 80 + 2 * 48 + 4 * 3 * 6
    flops, nbytes = builder.scan_pass_work(4, 1, 2, 3, 4)
    assert flops == 2 * macs
    # q, k (2 wide) and v, o (3 wide) in bf16, g and beta in float32, one
    # 2x3 float32 state.
    assert nbytes == 4 * ((2 + 2 + 3 + 3) * 2 + 2 * 4) + 6 * 4
    # Two chunks, five heads: everything but the state count by tokens.
    f2, b2 = builder.scan_pass_work(8, 5, 2, 3, 4)
    assert f2 == 10 * flops and b2 == 10 * nbytes
    assert builder.scan_pass_work(6, 1, 2, 3, 4)[1] == \
        6 * 28 + 2 * 24                     # a padded chunk keeps a state
    assert builder.causal_pairs(6) == 21
    cell = manifest.Cell(CELL)
    c, seq = cell.config, 8192
    assert builder.layers(c) == ["linear_attention"] * 3 + ["full_attention"]
    assert builder.scan_passes(c) == 4
    assert builder.scan_passes({**c, "remat": False}) == 3
    per_pass = builder.scan_pass_work(seq, 15, 96, 192, 64)
    assert per_pass[0] == 2 * 15 * seq * (64 * 864 + 3 * 96 * 192)
    assert builder.scan_work_per_step(c, 1, seq) == (
        12 * per_pass[0], 12 * per_pass[1])
    # ISSUE 30's arithmetic, forward FLOPs a token: the MLP 253.6M of a
    # layer's 342.4M in matrices (74%), the scan 3.3M a linear layer.
    linear, full, mlp = builder.matrix_parameters(c)
    assert round(2 * mlp / 1e6, 1) == 253.6
    assert round(100 * mlp / (mlp + linear)) == 74
    assert round(per_pass[0] / seq / 1e6, 1) == 3.3
    total = builder.train_flops_per_step(c, 1, seq)
    dense = 6 * seq * (3 * linear + full + 4 * mlp + 3840 * 12544)
    attention = 12 * 128 * 15 * builder.causal_pairs(seq)
    assert total == dense + 3 * 3 * per_pass[0] + attention
    assert 3.6e13 < total < 3.7e13


# ------------------------------------------------- the readers, by hand

STEP = "jit(train_step)/shard_map/"
BLOCK = STEP + "transpose(jvp(OlmoHybridLM))/layer_0/mixer/"
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_conv (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %c.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{BLOCK}hvd.linattn.conv/mul"}}
}}

%fused_gate (p0: f32[8]) -> f32[8] {{
  %p0.1 = f32[8]{{0}} parameter(0)
  %g.1 = f32[8]{{0}} multiply(%p0.1, %p0.1), metadata={{op_name="{BLOCK}hvd.linattn.gate/mul"}}
  ROOT %m.1 = f32[8]{{0}} multiply(%g.1, %p0.1), metadata={{op_name="{BLOCK}hvd.linattn.scan/transpose"}}
}}

%body (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %dot.1 = f32[8]{{0}} multiply(%p, %p), metadata={{op_name="{BLOCK}hvd.linattn.scan/while/body/dot_general"}}
}}

ENTRY %main (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_conv
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_gate
  %while.1 = f32[8]{{0}} while(%fusion.2), body=%body, metadata={{op_name="{BLOCK}hvd.linattn.scan/while"}}
  %gate.2 = f32[8]{{0}} multiply(%while.1, %a), metadata={{op_name="{STEP}jvp(OlmoHybridLM)/layer_0/mixer/hvd.linattn.gate/mul"}}
  ROOT %other = f32[8]{{0}} add(%gate.2, %a), metadata={{op_name="{BLOCK}wo/dot_general"}}
}}
'''
# One device, two steps; ns. The loop's own event covers its body's
# (dot.1, twice) and the time between them; fusion.2 holds an operation of
# the scan and one of the gate: it counts with the scan.
HAND_EVENTS = [("fusion.1", 0, 100), ("fusion.2", 100, 300),
               ("while.1", 400, 1000), ("dot.1", 450, 200),
               ("dot.1", 900, 200), ("gate.2", 1400, 50),
               ("other", 1450, 500)]


def _hand_run():
    trace = Trace({"/device:TPU:0": HAND_EVENTS}, [], {}, set())
    return {"trace": trace, "compiled_text": HAND_TEXT, "steps": 2,
            "cell": manifest.Cell(CELL), "chips": 1,
            "stamp": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def _read(name, run):
    return manifest.load_module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name,expected", [
    ("linattn_scan_ms", (300 + 1000) / 2 / 1e6),
    ("linattn_pointwise_ms", (100 + 50) / 2 / 1e6),
])
def test_readers_on_the_hand_run(name, expected):
    assert _read(name, _hand_run()) == pytest.approx(expected)


def test_roofline_on_the_hand_run(capsys):
    run = _hand_run()
    builder = manifest.load_module("builders", "olmo_hybrid_adamw")
    flops, nbytes = builder.scan_work_per_step(run["cell"].config, 1, 8192)
    least = max(flops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12
    assert _read("linattn_scan_roofline", run) == pytest.approx(
        100 * least / (1300 / 2 / 1e9))
    assert "bound by bytes" in capsys.readouterr().out


def test_readers_return_nothing_on_a_program_without_the_scopes():
    """The parent's programs plant no such scope: every new reader returns
    ``None`` and raises nothing; so does a run with no device trace."""
    run = _hand_run()
    run["compiled_text"] = HAND_TEXT.replace("hvd.linattn.", "linattn.")
    for name in NEW_METRICS:
        assert _read(name, run) is None
    untraced = dict(_hand_run(), trace=None)
    for name in NEW_METRICS:
        assert _read(name, untraced) is None
