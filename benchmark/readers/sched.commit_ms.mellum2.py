"""`sched.commit_ms.sat`'s reading, in `mellum2-reason-long` (an entry of its own, and
why: `_mellum.same_as`)."""
from benchmark.readers import _mellum

read = _mellum.same_as("sched.commit_ms.sat")
