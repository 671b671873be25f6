"""Admission and building a dispatch's inputs on the host (planning, prefix
acquire, page allocation; ids, window pages, tables): summed `sched.admit` +
`sched.build` per `sched.step`, median over the window."""
from benchmark.readers import _phases


def read(ctx):
    return _phases.phase_ms(ctx, ("sched.admit", "sched.build"))
