"""Share of its roofline the head's sweep reaches in a looped decoder, in
percent: the least time the chip could take for the sweep's three
vocabulary-wide products a chunk, over the time the trace gives
everything under ``hvd.loss.head`` (``loop_head_ms``).

Work, from shapes, by the configuration's builder (``loop_head_work``):
``6 x rows x hidden x vocabulary`` FLOPs over the ``passes x batch x
sequence`` rows of the stacked exits; bytes the kernel once a chunk and
the rows' states and gradients once. Nothing is recomputed in the sweep,
so the share is the model's too. The least time is the larger of FLOPs
over the bf16 peak and bytes over the HBM peak; the printed line says
which bounds."""

from harness import device, manifest
from layer_metrics import loop_head_ms


def share_of_least(run, name, ms, flops, nbytes, said=""):
    """``ms`` a step against the least the chip could take for ``flops``
    and ``nbytes``, in percent; prints which of the two bounds."""
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[{name}] {said}bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: least "
          f"{max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)


def read(run):
    ms = loop_head_ms.read(run)
    if not ms:
        return None
    config, traffic = run["cell"].config, run["cell"].traffic
    builder = manifest.load_module("builders", config["builder"])
    flops, nbytes = builder.loop_head_work(
        config, traffic["per_chip_batch"], traffic["sequence_length"])
    return share_of_least(run, "loop_head_roofline", ms, flops, nbytes)
