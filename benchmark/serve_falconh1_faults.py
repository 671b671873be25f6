"""python3 benchmark/serve_falconh1_faults.py --workload <name> --seed <n> --seconds <s>

The readings behind `drivers/serve_falconh1.py::LIMITS`, on the machine it is
started on: one window of the cell as `run.py` runs it, then the requests and the state
rows that decide `correct` held to the sound reference, to the reference with each
planted fault (`reference_falcon_h1.FAULTS`) and to the lower-precision
control. Every one but the first has to break a limit to be seen. `--faults
a,b` reads only those (and the sound one and the control). The last line of
stdout is `{"seed", "limits", "readings": {"sound" | "control" | fault:
reading}, "end_to_end": the window's metrics, "passes": [what kept every
limit]}`.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[0] = os.path.dirname(HERE)

from benchmark import reference_falcon_h1 as reference  # noqa: E402
from benchmark import run  # noqa: E402


def fault_readings(driver, m, p, samples, rows, pad_to, dtype,
                   faults=reference.FAULTS) -> dict:
    """{"sound" | "control" | fault: `driver.reading` of the same samples
    and held rows under that reference}."""
    def read(*faults):
        return driver.reading(m, p, samples, rows, pad_to, faults)

    with reference.lower_precision(driver.BELOW[dtype]):
        control = read()
    return {"sound": read(), "control": control,
            **{f: read(f) for f in faults}}


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
    m = cell["config"]
    samples = driver.sample_requests(raw["measured"],
                                     m["deployment"]["token_budget"])
    rows = raw.pop("held_rows")
    if bad or not samples or not rows:
        raise SystemExit(f"benchmark: the window itself failed: {bad}")
    limits = driver.LIMITS[dtype]
    run.say(f"faults: requests of prompts {[len(q) for q, _, _ in samples]}"
            f", tokens {[len(t) for _, t, _ in samples]}; held rows of "
            f"contexts {[len(i) for i, _, _ in rows]}, prompts "
            f"{[n for _, n, _ in rows]}")
    every = fault_readings(driver, m, p, samples, rows, pad_to, dtype,
                           [f for f in args.faults.split(",") if f])
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
