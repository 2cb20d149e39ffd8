"""Rows of the sorted order that the expert layer's walked rules visit
(the combine and the gradients of both row movements), over the rows the
order has (tokens x chosen), in percent, the sparse layers of the last
checked step together (``moe_load``, which the program counts and
returns with its state). The chunk and the number of chunks a layer's
landed rows take are the program's own: ``parallel/moe.py``'s
``walk_chunk`` and ``chunks_walked``, the functions its rules call, at
the cell's tokens, chosen experts, held experts and router width. An
even router reads one chunk above the held share (a chunk is a quarter
of it); 100 where the program moves every row: a share above an eighth,
or every assignment landed here. ``None`` from a program that has no
such functions."""


def read(run):
    try:
        from horovod_tpu.parallel.moe import chunks_walked, walk_chunk
    except ImportError:
        return None
    load = run.get("moe_load")
    if not load:
        return None
    cell = run["cell"]
    rows = (run["bench"].samples_per_step * cell.traffic["sequence_length"]
            * cell.config["num_experts_per_tok"])
    chunk = walk_chunk(rows, len(load[0]),
                       cell.config["deployment"]["router_width"])
    if not chunk:
        return 100.0
    walked = [min(rows, chunk * int(chunks_walked(sum(layer), chunk)))
              for layer in load]
    print(f"[moe_held_walked_pct] chunks of {chunk} rows; walked by sparse "
          f"layer {walked} of {rows}", flush=True)
    return 100.0 * sum(walked) / (rows * len(load))
