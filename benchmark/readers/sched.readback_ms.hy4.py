"""`sched.readback_ms`'s reading, in `hy4-longctx-reason` (an entry of its own,
and why: `_mellum.same_as`)."""
from benchmark.readers import _mellum

read = _mellum.same_as("sched.readback_ms")
