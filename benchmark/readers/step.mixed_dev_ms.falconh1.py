"""`step.mixed_dev_ms.sat`'s reading, in `falconh1-chat-sat` (an entry of
its own, and why: `_mellum.same_as`)."""
from benchmark.readers import _mellum

read = _mellum.same_as("step.mixed_dev_ms.sat")
