"""`step.decode_dev_ms.sat`'s reading, in `hy4-longctx-reason` (an entry of
its own, and why: `_mellum.same_as`)."""
from benchmark.readers import _mellum

read = _mellum.same_as("step.decode_dev_ms.sat")
