"""python3 benchmark/serve_hy4_faults.py --workload <name> --seed <n> --seconds <s>

The readings behind `drivers/serve_hy4.py::LIMITS`, on the machine it is
started on: one window of the cell as `run.py` runs it, then the requests that
decide `correct` held to the sound reference, to the reference with each
planted fault (`reference_hy_v4.FAULTS`) and to the lower-precision control.
Every one but the first has to break a limit. `--faults a,b` reads only those
(and the sound one and the control). The last line of stdout is `{"seed",
"limits", "readings": {"sound" | "control" | fault: reading}, "end_to_end":
the window's metrics, "passes": [what kept every limit]}`; `passes` has to
be `["sound"]`.
"""
import argparse
import json
import os
import sys

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[0] = os.path.dirname(HERE)

from benchmark import reference_hy_v4 as reference  # noqa: E402
from benchmark import run  # noqa: E402


def fault_readings(driver, m, p, samples, pad_to, dtype,
                   faults=reference.FAULTS) -> dict:
    """{"sound" | "control" | fault: `driver.position_readings` of the same
    samples under that reference}."""
    read = driver.position_readings
    with reference.lower_precision(driver.BELOW[dtype]):
        control = read(m, p, samples, pad_to)
    return {"sound": read(m, p, samples, pad_to), "control": control,
            **{f: read(m, p, samples, pad_to, (f,)) for f in faults}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default=",".join(reference.FAULTS))
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.check_device(cell["chips"])
    driver = run.load_module("drivers", cell["mix"]["driver"])
    raw, _, bad, p, dtype, pad_to = driver.serve(
        cell, args.seed, args.seconds, False, run.say)
    samples = driver.sample_requests(raw["measured"])
    if bad or not samples:
        raise SystemExit(f"benchmark: the window itself failed: {bad}")
    host = jax.device_get(p)
    for a in jax.tree.leaves(p):
        a.delete()
    limits = driver.LIMITS[dtype]
    at = fault_readings(driver, cell["config"], host, samples, pad_to, dtype,
                        [f for f in args.faults.split(",") if f])
    every = {name: driver.readings(*pair) for name, pair in at.items()}
    for name, reading in every.items():
        run.say(f"faults: {name}: {reading}; over {limits}: "
                f"{driver.over(reading, limits)}")
    out = {"seed": args.seed, "limits": limits, "readings": every,
           "end_to_end": driver.end_to_end(raw),
           "passes": [name for name, r in every.items()
                      if not driver.over(r, limits)]}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
