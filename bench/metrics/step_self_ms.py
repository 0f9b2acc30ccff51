"""Mean self time of the ``engine.step`` spans (host clock): each step's
duration less that of its direct children (assembly, execution,
delivery), found by interval containment on the step's thread. What is
left is the step's own bookkeeping: picking the batch, building
results, the engine's counters."""
from bisect import bisect_left, bisect_right

from bench.record import mean


def read(run):
    spans = sorted(run.spans, key=lambda e: e.ts_ns)
    starts = [e.ts_ns for e in spans]
    out = []
    for step in spans:
        if step.name != "engine.step":
            continue
        end = step.ts_ns + step.dur_ns
        inside = spans[bisect_left(starts, step.ts_ns):
                       bisect_right(starts, end)]
        children = sum(e.dur_ns for e in inside
                       if e.tid == step.tid and e.depth == step.depth + 1
                       and e.ts_ns + e.dur_ns <= end)
        out.append((step.dur_ns - children) / 1e6)
    return mean(out)
