"""The busiest held expert's rows over the held experts' mean, a step: how
uneven the rows the grouped matmul gets are."""
from benchmark.readers import _moe


def read(ctx):
    ratios = [r["moe.load_max"] / r["moe.load_mean"]
              for r in _moe.reports(ctx) if r.get("moe.load_mean")]
    return sum(ratios) / len(ratios) if ratios else None
