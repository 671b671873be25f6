"""Committing a chunk on the host (token lists, retirements, prefix inserts,
slot bind): summed `sched.commit` per `sched.step`, median over the window."""
from benchmark.readers import _phases


def read(ctx):
    return _phases.phase_ms(ctx, ("sched.commit",))
