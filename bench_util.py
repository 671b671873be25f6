"""Shared bench timing: the paired-slope decode/op timer.

One implementation of the op-gate discipline (bench.py `_op_bench`
round-4 lessons): cost = (t_hi - t_lo) / span, measured as ADJACENT
lo/hi pairs so a drifting fixed per-call cost cancels within a pair,
median across pairs so one drifty window cannot set the number. Every
bench that quotes a per-step or per-iter figure uses this — the
round-3/4 serving "drift" and the round-4 rms_norm false flag were both
re-implemented timers diverging from this discipline.
"""
from __future__ import annotations

import time


def paired_slope_ms(run, lo, hi, pairs: int = 8):
    """Median over `pairs` of ((t(run(hi)) - t(run(lo))) / (hi - lo)),
    in milliseconds. `run(n)` must BLOCK until the device result is real
    (np.asarray / float of a device value, or block_until_ready — both
    are true barriers on the v5e, measured by chip_smoke.py in PR 22).
    Call sites warm both legs
    (compile + cache) before timing."""
    span = hi - lo
    slopes = []
    for _ in range(pairs):
        t0 = time.perf_counter(); run(lo)
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter(); run(hi)
        t_hi = time.perf_counter() - t0
        slopes.append(max(t_hi - t_lo, 0.0) / span * 1e3)
    slopes.sort()
    mid = len(slopes) // 2
    return slopes[mid] if len(slopes) % 2 else \
        (slopes[mid - 1] + slopes[mid]) / 2


def pop_trace_arg(argv, usage: str):
    """Extract `--trace PATH` from an argv list in place; returns the
    path or None. Shared by bench_continuous/bench_serving (ISSUE 8)
    so the missing-path usage error stays in one place."""
    import sys

    if "--trace" not in argv:
        return None
    i = argv.index("--trace")
    if i + 1 >= len(argv):
        sys.exit(usage + "  (--trace needs a path)")
    path = argv[i + 1]
    del argv[i:i + 2]
    return path


def hist_percentiles_ms(hist, qs=(50, 90, 99)):
    """An observability Histogram's percentiles in rounded ms for a
    bench JSON row; None when the histogram is empty."""
    if not hist.count:
        return None
    return {k: (None if v is None else round(v * 1e3, 2))
            for k, v in hist.percentiles(qs).items()}
