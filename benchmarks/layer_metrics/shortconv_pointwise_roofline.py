"""Share of their roofline the gated short convolutions' pointwise passes
reach, in percent: the least time the chip could take for the bytes the
passes have to move, over the time the trace gives the operations under
``hvd.shortconv.pointwise`` (``shortconv_pointwise_ms``).

Work, from shapes, by the configuration's builder
(``shortconv_pointwise_work``): every convolution layer's forward pass
reads the two gates and ``u`` and writes the gated result, its backward
pass reads those three and the result's gradient and writes three
gradients, arrays of tokens x hidden in bf16; the block's recomputed
forward reads what the backward reads anyway and adds nothing to the
least. What the step moves beyond that (float32 intermediates written
out, the recomputed forward as a pass of its own) shows as a low share.
The least time is the larger of FLOPs over the bf16 peak and bytes over
the HBM peak; the printed line says which bounds."""

from harness import device, manifest
from layer_metrics import shortconv_pointwise_ms


def read(run):
    ms = shortconv_pointwise_ms.read(run)
    if not ms:
        return None
    config, traffic = run["cell"].config, run["cell"].traffic
    builder = manifest.load_module("builders", config["builder"])
    flops, nbytes = builder.shortconv_pointwise_work(
        config, traffic["per_chip_batch"], traffic["sequence_length"])
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[shortconv_pointwise_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: least "
          f"{max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
