"""The chunked loss head (PR 31) compiled for a described TPU v5e at both
decoder cells' shapes: the loss alone under ``jax.value_and_grad``, not a
cell's step (``test_aot_smallthinker.py`` and ``test_aot_olmo_hybrid.py``
compile those). One sweep over the sequence's chunks computes the loss
and both gradients: one ``while`` under ``hvd.loss.head``, three
vocabulary-wide products in its body. Nothing runs; nothing here is a
measurement. The fixtures are ``test_aot_v5e.py``'s (the topology is
described inside a fixture, never at import: on-chip-measurement guide,
section 2). Below, ``loss_head_ms``'s reader on a hand-made run."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from harness import manifest, scope_time, scopes
from harness.trace_reduce import Trace
from test_aot_v5e import no_compile_cache, topo  # noqa: F401

SCOPE = "hvd.loss.head"
# The cells whose step calls the head: hidden (2, 8192, 2560) x kernel
# (2560, 37984), and (1, 8192, 3840) x (3840, 12544), read from their files.
CELLS = ("olmo-hybrid-7b-s8k-tp2share", "smallthinker-21b-a3b-s8k-ep4share")
WHILE_RE = re.compile(r"=\s.*\swhile\(.*\bbody=%?([\w.\-]+)")
PRODUCT_RE = re.compile(r"=\s*(\S+)\s+(?:convolution|dot)\(([^)]*)\)")
INSTRUCTION = scopes.INSTRUCTION_RE
SHAPE_OF_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+\[[\d,]*\])",
                         re.M)


def _shapes_of_the_cell(cell_name):
    cell = manifest.Cell(cell_name)
    config, traffic = cell.config, cell.traffic
    hidden = (traffic["per_chip_batch"], traffic["sequence_length"],
              config["hidden_size"])
    return (hidden, (config["hidden_size"], config["vocab_size"]),
            config["loss_chunks"], config["compute_dtype"])


@pytest.fixture(scope="module", params=CELLS)
def compiled(request, topo, no_compile_cache):  # noqa: F811
    from horovod_tpu.models import chunked_causal_lm_loss

    hidden, kernel, chunks, dtype = _shapes_of_the_cell(request.param)
    one_chip = SingleDeviceSharding(topo.devices[0])
    step = jax.jit(jax.value_and_grad(
        lambda h, w, ids: chunked_causal_lm_loss(h, w, ids,
                                                 num_chunks=chunks),
        argnums=(0, 1)))
    return kernel[1], step.lower(
        jax.ShapeDtypeStruct(hidden, jnp.dtype(dtype), sharding=one_chip),
        jax.ShapeDtypeStruct(kernel, jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct(hidden[:2], jnp.int32, sharding=one_chip),
    ).compile()


def _loops(text):
    return [line for line in text.splitlines() if WHILE_RE.search(line)]


def _products_under(text, computation):
    """Lines of the matrix products (the TPU compiler writes a dot as a
    ``convolution``) in ``computation`` and in what it calls."""
    _, calls, members = scopes._parse(text)
    lines = {INSTRUCTION.match(line).group(1): line
             for line in text.splitlines() if INSTRUCTION.match(line)}
    found, todo, seen = [], [computation], set()
    while todo:
        here = todo.pop()
        if here in seen:
            continue
        seen.add(here)
        for name in members.get(here, ()):
            if PRODUCT_RE.search(lines[name]):
                found.append(lines[name])
            if name in calls:
                todo.append(calls[name])
    return found


def _dims(shape):
    return [int(x) for x in re.search(r"\[([\d,]*)\]", shape).group(1)
            .split(",") if x]


def test_one_loop_and_it_carries_the_scope(compiled):
    _, program = compiled
    text = program.as_text()
    loops = _loops(text)
    assert len(loops) == 1
    assert SCOPE in scopes.OP_NAME_RE.search(loops[0]).group(1)
    # The reader counts the loop whole: by the loop's own name.
    assert INSTRUCTION.match(loops[0]).group(1) in scope_time.names_under(
        text, (SCOPE,))
    assert not re.search(r"=\s.*\sconditional\(", text)


def test_three_vocabulary_wide_products_in_the_loops_body(compiled):
    vocab, program = compiled
    text = program.as_text()
    body = WHILE_RE.search(_loops(text)[0]).group(1)
    shape_of = dict(SHAPE_OF_RE.findall(text))
    wide = []
    for line in _products_under(text, body):
        result, operands = PRODUCT_RE.search(line).groups()
        shapes = [result] + [shape_of[name.strip().lstrip("%")]
                             for name in operands.split(",")]
        if any(vocab in _dims(shape) for shape in shapes):
            wide.append(line)
    assert len(wide) == 3, wide
    assert all(SCOPE in line for line in wide)
    # ... and none outside the loop: nothing of the head is left over.
    entry = next(name for name in scopes._parse(text)[2]
                 if re.search(rf"^ENTRY\s+%?{re.escape(name)}\s", text, re.M))
    assert not _products_under(text, entry)


def test_the_sweeps_temporaries_stay_a_chunk_wide(compiled):
    _, program = compiled
    assert program.memory_analysis().temp_size_in_bytes < 1.5e9


# ------------------------------------------------- the reader, by hand

STEP = "jit(train_step)/shard_map/"
HAND_TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_sweep (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %c.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{STEP}jvp(hvd.loss.head)/while/body/closed_call/dot_general"}}
}}

%fused_scale (p0: f32[8]) -> f32[8] {{
  %p0.1 = f32[8]{{0}} parameter(0)
  %g.1 = f32[8]{{0}} multiply(%p0.1, %p0.1), metadata={{op_name="{STEP}transpose(jvp(hvd.loss.head))/mul"}}
  ROOT %m.1 = f32[8]{{0}} multiply(%g.1, %p0.1), metadata={{op_name="{STEP}transpose(jvp(SmallThinkerLM))/norm/mul"}}
}}

%body (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %fusion.9 = f32[8]{{0}} fusion(%p), kind=kOutput, calls=%fused_sweep
}}

ENTRY %main (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %copy.1 = f32[8]{{0}} copy(%a), metadata={{op_name="{STEP}jvp(hvd.loss.head)/transpose"}}
  %while.1 = f32[8]{{0}} while(%copy.1), body=%body, metadata={{op_name="{STEP}jvp(hvd.loss.head)/while"}}
  %fusion.2 = f32[8]{{0}} fusion(%while.1), kind=kLoop, calls=%fused_scale
  %head.2 = f32[8]{{0}} multiply(%fusion.2, %a), metadata={{op_name="{STEP}jvp(SmallThinkerLM)/hvd.loss.header/mul"}}
  ROOT %other = f32[8]{{0}} add(%head.2, %a), metadata={{op_name="{STEP}transpose(jvp(SmallThinkerLM))/lm_head/dot_general"}}
}}
'''
# One device, two steps; ns. The loop's own event covers its body's
# (fusion.9, twice) and the time between them; fusion.2 holds an operation
# of the head and one of the model: it counts with the head. A scope that
# only begins with the head's name ("hvd.loss.header") is another scope.
HAND_EVENTS = [("copy.1", 0, 100), ("while.1", 100, 1000),
               ("fusion.9", 150, 300), ("fusion.9", 600, 300),
               ("fusion.2", 1200, 60), ("head.2", 1300, 50),
               ("other", 1400, 500)]


def _hand_run(cell="smallthinker-21b-a3b-s8k-ep4share"):
    trace = Trace({"/device:TPU:0": HAND_EVENTS}, [], {}, set())
    return {"trace": trace, "compiled_text": HAND_TEXT, "steps": 2,
            "cell": manifest.Cell(cell), "chips": 1,
            "stamp": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def _read(run):
    return manifest.load_module("layer_metrics", "loss_head_ms").read(run)


def test_reader_on_the_hand_run():
    assert _read(_hand_run()) == pytest.approx((100 + 1000 + 60) / 2 / 1e6)


@pytest.mark.parametrize("change", [
    lambda run: run.update(
        compiled_text=HAND_TEXT.replace("hvd.loss.head", "loss.head")),
    lambda run: run.update(trace=None),
], ids=["no_scope", "no_trace"])
def test_reader_returns_nothing_and_raises_nothing(change):
    """The parent's programs plant no such scope; an untraced run has no
    device time to read."""
    run = _hand_run()
    change(run)
    assert _read(run) is None


def test_the_metric_lists_the_two_decoder_cells():
    entry = next(p for p in manifest.load_manifest()["per_layer"]
                 if p["name"] == "loss_head_ms")
    assert sorted(entry["workloads"]) == sorted(CELLS)
    assert entry["moves"] == "train_samples_per_s_per_chip"
    for cell in CELLS:
        assert "loss_head_ms" in [p["name"]
                                  for p in manifest.Cell(cell).per_layer]
