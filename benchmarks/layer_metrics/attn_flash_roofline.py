"""Share of their roofline the flash-attention kernels reach under a
causal band, in percent: the least time the chip could take for the calls
the step makes, over the time the trace gives them.

Work a call, from shapes and the layer's kind: the query-key pairs inside
the band (``builders/smallthinker_adamw.band_pairs``: every j <= i on a
global layer, ``i - window < j <= i`` on a windowed one) times 4 x head
width FLOPs in the forward (scores and context), 6 x in backward-dq, 8 x
in backward-dkdv, for every query head and sequence. A forward call that
the compiled step makes twice (the block recomputed in the backward pass)
is counted twice: the share is the kernels', not the model's. Bytes: each
call's operands and results once in bf16 (K and V at their own head
count) with the f32 row statistics. The least time is the larger of FLOPs
over the bf16 peak and bytes over the HBM peak."""

from harness import device, manifest, scopes
from layer_metrics import attn_flash_ms


def flash_band_work(batch, heads, kv_heads, seq, width, pairs,
                    forward_calls):
    """``(flops, bytes)`` of one layer's calls a step: ``forward_calls``
    forward, one dq and one dkdv, over ``pairs`` query-key pairs a head
    and sequence."""
    q_tile = batch * heads * seq * width * 2        # one bf16 q/o/do/dq
    kv_tile = batch * kv_heads * seq * width * 2    # one bf16 k/v/dk/dv
    stat = batch * heads * seq * 4                  # one f32 row statistic
    flops = (4 * forward_calls + 6 + 8) * batch * heads * pairs * width
    forward = 2 * q_tile + 2 * kv_tile + stat           # q k v -> o, lse
    backward_dq = 3 * q_tile + 2 * kv_tile + 2 * stat   # q k v do .. -> dq
    backward_dkdv = 2 * q_tile + 4 * kv_tile + 2 * stat     # ... -> dk, dv
    return flops, forward_calls * forward + backward_dq + backward_dkdv


def read(run):
    ms = attn_flash_ms.read(run)
    trace = run.get("trace")
    if not ms or trace is None:
        return None
    config, traffic = run["cell"].config, run["cell"].traffic
    builder = manifest.load_module("builders", config["builder"])
    windows = builder.layer_windows(config)
    forward_calls = len(scopes.kernel_names(
        trace, run["compiled_text"], attn_flash_ms.KERNELS[0])) / len(windows)
    flops = nbytes = 0
    for window in windows:
        f, b = flash_band_work(
            traffic["per_chip_batch"], config["num_attention_heads"],
            config["num_key_value_heads"], traffic["sequence_length"],
            config["head_dim"],
            builder.band_pairs(traffic["sequence_length"], window),
            forward_calls)
        flops, nbytes = flops + f, nbytes + b
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[attn_flash_roofline] {forward_calls:g} forward calls a layer; "
          f"bound by {'flops' if by_flops >= by_bytes else 'bytes'}: least "
          f"{max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
