"""What the host adds to one scheduling iteration: the `sched.step` span less
the `decode.sync_wait` inside it (the host blocked on the device), median
over the window."""
from benchmark import arith
from benchmark.readers import _phases


def read(ctx):
    per_step = _phases.step_phase_ms(ctx, ("decode.sync_wait",))
    if not per_step:
        return None
    return arith.median([step - wait for step, wait in per_step])
