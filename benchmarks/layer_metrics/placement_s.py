"""Host seconds before the window inside the program's mesh and
placement calls: its spans ``make_mesh``, ``replicate`` and
``shard_batch`` (``parallel/mesh.py``). ``device_put`` returns before
the copy lands, so this is what the host pays, not the transfer."""

from harness import program_log


def read(run):
    return program_log.span_seconds(
        run, ("make_mesh", "replicate", "shard_batch"))
