"""One traced run of a cell as tables: device milliseconds a step by phase,
by module path and by kernel name, and set-up by the program's own spans
and compile records. A tool for the person who writes PERF.md's "where
the time goes", not a metric.

    python benchmarks/tools/scope_table.py --workload <cell> --seed <n>
        [--seconds 30] [--depth 2] [--rows 20] [--rehearse-cpu]
    python benchmarks/tools/scope_table.py --from .bench_out/<cell>
        [--depth 2] [--rows 20] [--head <events> <out.json.gz>]

The first form makes the run itself, through ``run.py``'s own ``main`` with
``--trace 1`` (so the run's usual lines and its result are printed first,
and it needs the chip), then keeps beside the trace in
``.bench_out/<cell>/`` what the tables are made from: ``compiled.txt.gz``
(the step's compiled text), ``trace.json.gz`` (the reduced trace) and
``program_log.json`` (the span log, the compile records, the window's
start and the steps). The second form prints the tables again from those
files, anywhere. ``--head`` also writes a test fixture: the first events
of every device with the slice of the compiled text that explains them
(``program_log.json`` is small enough to be a fixture as it is).
"""

import argparse
import gzip
import json
import os
import re
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from harness import manifest, program_log, scopes, trace_reduce  # noqa: E402

TEXT, TRACE, LOG = "compiled.txt.gz", "trace.json.gz", "program_log.json"


# ------------------------------------------------------------ the run

def traced_run(args):
    """The cell's traced run through ``run.py``, with the builder's
    ``layer_inputs`` kept; returns them."""
    import run as bench_run

    kept = {}
    load = manifest.load_module

    def load_and_keep(kind, name):
        module = load(kind, name)
        if kind == "builders":
            inner = module.run

            def run(ctx):
                result = inner(ctx)
                # The same dict ``run.py`` goes on to complete.
                kept["inputs"] = result["layer_inputs"]
                kept["cell"] = ctx["cell"]
                return result

            module.run = run
        return module

    manifest.load_module = load_and_keep
    try:
        code = bench_run.main([
            "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "1"]
            + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    finally:
        manifest.load_module = load
    if code or "trace" not in kept.get("inputs", {}):
        raise SystemExit(f"scope_table: the run gave no trace (exit {code})")
    return kept["inputs"], kept["cell"]


def reading_cost(run, cell):
    """What the per-layer readers cost after the window: every reader of
    the cell once more, from a cold parse of the compiled text."""
    for cached in (scopes.phases, scopes.phases_planted, scopes.op_names,
                   scopes._parse):
        cached.cache_clear()
    t0 = time.perf_counter()
    for metric in cell.per_layer:
        manifest.load_module("layer_metrics", metric["name"]).read(run)
    seconds = time.perf_counter() - t0
    spans = program_log.spans(run) or []
    events = program_log.compile_events(run) or []
    print(f"[scope_table] reading {len(cell.per_layer)} per-layer metrics "
          f"took {seconds:.3f} s; the program kept {len(spans)} spans and "
          f"{len(events)} compile records")


def save(run, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    with gzip.open(os.path.join(out_dir, TEXT), "wt") as f:
        f.write(run["compiled_text"])
    with gzip.open(os.path.join(out_dir, TRACE), "wt") as f:
        json.dump(run["trace"].to_json(), f, separators=(",", ":"))
    with open(os.path.join(out_dir, LOG), "w") as f:
        json.dump({
            "steps": run["steps"],
            "window": {"start": run["window"]["start"],
                       "end": run["window"]["end"]},
            "host_spans": dict(run["spans"]),
            "program_spans": program_log.spans(run),
            "program_compile_events": program_log.compile_events(run),
        }, f, indent=1)


def load(out_dir):
    with gzip.open(os.path.join(out_dir, TEXT), "rt") as f:
        text = f.read()
    with open(os.path.join(out_dir, LOG)) as f:
        run = json.load(f)
    run["compiled_text"] = text
    run["trace"] = trace_reduce.load(os.path.join(out_dir, TRACE))
    for key in ("program_spans", "program_compile_events"):
        if run[key] is not None:    # None: an older program kept none
            run[key] = [tuple(r) for r in run[key]]
    return run


# ------------------------------------------------------------- tables

def _table(title, header, rows):
    print(f"\n{title}")
    cells = [header] + [[f"{c:.3f}" if isinstance(c, float) else str(c)
                         for c in row] for row in rows]
    widths = [max(len(r[i]) for r in cells) for i in range(len(header))]
    for r in cells:
        print("  " + "  ".join(c.ljust(w) if i == 0 else c.rjust(w)
                               for i, (c, w) in enumerate(zip(r, widths))))


def module_of(own, inner, depth):
    """One module path for an instruction: that of its own ``op_name``
    (for a fusion, the instruction XLA named it by), or else the path
    most of its inner instructions carry."""
    path = scopes.module_path(own, depth) if own else "(top)"
    if path != "(top)" or not inner:
        return path
    paths = [scopes.module_path(o, depth) for o in sorted(inner)]
    paths = [p for p in paths if p != "(top)"]
    return max(sorted(set(paths)), key=paths.count) if paths else "(top)"


def per_step_ms(trace, steps, key):
    """key(instruction name) -> device ms a step, the operations'
    durations summed, mean over the devices."""
    sums = {}
    for events in trace.devices.values():
        for name, _, d in events:
            k = key(name)
            sums[k] = sums.get(k, 0) + d
    n = max(len(trace.devices), 1) * steps * 1e6
    return {k: v / n for k, v in sums.items()}


def device_tables(run, depth, rows):
    trace, text, steps = run["trace"], run["compiled_text"], run["steps"]
    if not trace.devices:
        print("\nno device plane in the trace: no device table")
        return
    busy = trace_reduce.mean_busy_ns(trace) / steps / 1e6
    by_phase = scopes.phase_ns(trace, text)
    order = [p for p, _ in scopes.PHASES][::-1] + [scopes.NONE, scopes.MIXED]
    _table(f"device time a step by phase ({steps} steps, "
           f"{len(trace.devices)} device(s), busy {busy:.3f} ms)",
           ["phase", "ms", "% of busy"],
           [[p, by_phase[p] / steps / 1e6,
             100 * by_phase[p] / steps / 1e6 / busy]
            for p in order if p in by_phase]
           + [["sum", sum(by_phase.values()) / steps / 1e6,
               100 * sum(by_phase.values()) / steps / 1e6 / busy]])

    names = scopes.op_names(text)
    phases = scopes.phases(text)
    held = per_step_ms(trace, steps, lambda n: "+".join(sorted(
        {scopes.phase(o) for o in names.get(n, ())} - {scopes.NONE}))
        or scopes.NONE)
    _table("by the phases an operation's instructions fall in (forward + "
           "backward counts as backward, any other pair as mixed)",
           ["phases held", "ms", "% of busy"],
           [[k, ms, 100 * ms / busy]
            for k, ms in sorted(held.items(), key=lambda kv: -kv[1])])
    own = scopes.own_op_names(text)
    by_module = per_step_ms(
        trace, steps, lambda n: (module_of(own.get(n), names.get(n, ()),
                                           depth),
                                 phases.get(n, scopes.NONE)))
    modules = {}
    for (module, phase), ms in sorted(by_module.items()):
        modules.setdefault(module, {})[phase] = ms
    ranked = sorted(modules.items(), key=lambda kv: -sum(kv[1].values()))
    _table(f"device time a step by module path (depth {depth}), "
           f"top {rows} of {len(ranked)}",
           ["module", "ms", "forward", "backward", "update", "other"],
           [[m, sum(p.values()), p.get("forward", 0.0),
             p.get("backward", 0.0), p.get("update", 0.0),
             sum(v for k, v in sorted(p.items())
                 if k not in ("forward", "backward", "update"))]
            for m, p in ranked[:rows]])

    kernels = {}
    for n in sorted(trace.kernels):
        scope = sorted(names.get(n, {n}))[0].split("/")
        kernels.setdefault(scope[-2] if len(scope) > 1 else n, set()).add(n)
    if kernels:
        ms = per_step_ms(trace, steps, lambda n: n)
        _table("Mosaic kernels by name",
               ["kernel", "calls a step", "ms a step", "ms a call"],
               [[k, len(members), sum(ms.get(n, 0.0) for n in members),
                 sum(ms.get(n, 0.0) for n in members) / len(members)]
                for k, members in sorted(kernels.items())])


def setup_tables(run, rows):
    spans = program_log.spans(run)
    events = program_log.compile_events(run)
    start = program_log.window_start_ns(run)
    if spans:
        zero = min(s for _, s, _, _ in spans)
        _table("set-up by the program's spans (before the window)",
               ["span", "parent", "at s", "seconds"],
               [[name, parent or "-", (s - zero) / 1e9, (e - s) / 1e9]
                for name, s, e, parent in spans if e <= start])
    if events:
        per_fun = {}
        for event, fun, seconds, at in events:
            if seconds is None or at > start:
                continue
            fun = re.sub(r"^jit\((.*)\)$", r"\1", fun or "-")
            kind = event.rsplit("/", 1)[-1].replace("_duration", "")
            row = per_fun.setdefault(fun, {})
            row[kind] = row.get(kind, 0.0) + seconds
            row["n"] = row.get("n", 0) + (kind == "backend_compile")
        ranked = sorted(per_fun.items(), key=lambda kv: -sum(
            v for k, v in sorted(kv[1].items()) if k != "n"))
        kinds = ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile",
                 "cache_retrieval_time_sec")
        _table(f"compile records before the window by function, top {rows} "
               f"of {len(ranked)} (outermost regions only)",
               ["function", "programs", "trace s", "lower s", "backend s",
                "of it cache read s"],
               [[fun, row.get("n", 0)] + [row.get(k, 0.0) for k in kinds]
                for fun, row in ranked[:rows]])
        lower, backend = (
            manifest.load_module("layer_metrics", name).read(run)
            for name in ("compile_lower_s", "compile_backend_s"))
        counts = {k: sum(1 for e, _, _, at in events
                         if e.endswith(k) and at <= start)
                  for k in ("cache_hits", "cache_misses")}
        print(f"\n  tracing + lowering {lower:.3f} s, backend {backend:.3f} s"
              f" (unions), cache hits {counts['cache_hits']}, entries "
              f"written {counts['cache_misses']}; host spans of the "
              f"benchmark: " + " ".join(
                  f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                  for k, v in sorted(run.get("host_spans", {}).items())))


# ------------------------------------------------------------ fixtures

def slice_text(text, names):
    """The lines of ``text`` that define the instructions ``names`` and,
    whole, every computation they call: enough for ``scopes`` to say of
    these instructions what it says from the full text."""
    blocks, lines_of, calls = {}, {}, {}
    inside = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = scopes.COMPUTATION_RE.match(line)
            inside = m.group(1) if m else None
            if inside is not None:
                blocks[inside] = [line]
            continue
        if inside is None:
            continue
        blocks[inside].append(line)
        m = scopes.INSTRUCTION_RE.match(line)
        if m:
            lines_of[m.group(1)] = line
            called = scopes.CALLS_RE.search(line)
            if called:
                calls.setdefault(inside, []).append(called.group(1))
                calls[m.group(1)] = called.group(1)
    keep, todo = [], [calls[n] for n in sorted(names) if n in calls]
    while todo:
        block = todo.pop()
        if block in keep or block not in blocks:
            continue
        keep.append(block)
        todo.extend(calls.get(block, []))
    out = []
    for block in sorted(keep):
        out += blocks[block] + ["}", ""]
    out.append("ENTRY %slice () -> () {")
    out += [lines_of[n] for n in sorted(names) if n in lines_of]
    out.append("}")
    return "\n".join(out) + "\n"


def write_head(run, events, out):
    head = run["trace"].head(events)
    names = {n for v in head.devices.values() for n, _, _ in v}
    with gzip.open(out, "wt") as f:
        json.dump({"trace": head.to_json(),
                   "compiled_text": slice_text(run["compiled_text"], names)},
                  f, separators=(",", ":"))
    print(f"{out}: {os.path.getsize(out)} bytes, {len(names)} instructions")


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="walk the tool on the CPU; its times mean nothing")
    p.add_argument("--from", dest="from_dir")
    p.add_argument("--depth", type=int, default=2)
    p.add_argument("--rows", type=int, default=20)
    p.add_argument("--head", nargs=2, metavar=("EVENTS", "OUT"))
    args = p.parse_args()
    if bool(args.workload) == bool(args.from_dir):
        p.error("give --workload (makes the run) or --from (reads one)")
    if args.workload:
        run, cell = traced_run(args)
        if not args.rehearse_cpu:
            reading_cost(run, cell)
        out_dir = os.path.join(manifest.ROOT, ".bench_out", args.workload)
        save(run, out_dir)
        print(f"\n[scope_table] kept {TEXT}, {TRACE}, {LOG} in {out_dir}")
    run = load(args.from_dir or out_dir)
    device_tables(run, args.depth, args.rows)
    setup_tables(run, args.rows)
    if args.head:
        write_head(run, int(args.head[0]), args.head[1])


if __name__ == "__main__":
    main()
