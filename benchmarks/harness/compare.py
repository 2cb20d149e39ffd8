"""The comparison that decides ``correct`` for a training cell.

Each number compared is a gap with a limit of its own, printed beside it
in every run. Norms are compared leaf by leaf: the gap between the
program's norm and the reference's (not the norm of their difference),
against the reference's norm of that leaf or of the median leaf, whichever
is larger, since some gradients are all but zero.
"""

import math
import statistics


def leaf_gaps(program_norms, reference_norms):
    """leaf -> gap of its two norms, measured against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    if program_norms.keys() != reference_norms.keys():
        raise ValueError("program and reference trees differ: "
                         f"{sorted(set(program_norms) ^ set(reference_norms))}")
    floor = statistics.median(reference_norms.values())
    out = {}
    for leaf, ref in reference_norms.items():
        scale = max(ref, floor)
        out[leaf] = (abs(program_norms[leaf] - ref) / scale if scale > 0
                     else float(program_norms[leaf] != ref))
    return out


def worst(gaps):
    """``(gap, leaf)`` of the worst leaf; a NaN gap is the worst there
    is."""
    worst_gap, where = 0.0, None
    for leaf, gap in gaps.items():
        if not gap <= worst_gap:
            worst_gap, where = gap, leaf
    return worst_gap, where


def global_norm(norms):
    return math.sqrt(sum(v * v for v in norms.values()))


def relative_gap(program, reference):
    return abs(program - reference) / abs(reference)


def judge(numbers, limits):
    """``numbers``: name -> gap. Prints every gap beside its limit and
    returns whether all are inside theirs. A limit with no number is a
    fault of the benchmark, not a pass; a number with no limit is printed
    as read and not judged."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise ValueError(f"limits without a number: {missing}")
    ok = True
    for name, gap in numbers.items():
        if name not in limits:
            print(f"[check] {name} = {gap:.6g} (read, not judged)",
                  flush=True)
            continue
        inside = gap <= limits[name]    # NaN compares false
        ok = ok and inside
        print(f"[check] {name} = {gap:.6g} limit {limits[name]:.6g} "
              f"{'ok' if inside else 'NOT CORRECT'}", flush=True)
    return ok
