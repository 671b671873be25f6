"""python3 benchmark/train_afmoe_faults.py --workload <name> --seed <n> [--faults a,b]

The readings behind `drivers/train_afmoe.py::LIMITS` that have to FAIL, on
the machine it is started on and at the cell's sizes: the reference computed
one precision lower (the control) and the reference with each planted fault
(`reference_afmoe.FAULTS`; all of them, or those named), each held to the
sound reference under that run's routing on the probe row, as the driver's
`control` holds it. The sound program's readings are the cell's own run's
(`check:` lines of `run.py`). The last line of stdout is `{"seed", "limits",
"over": {"control" | fault: [the checks over their limit]}, "passes": [what
kept every limit]}`; `passes` has to be `[]`. A fault's reference compiles
anew (~2 min each on the chip, cold).
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[0] = os.path.dirname(HERE)

from benchmark import reference_afmoe as reference  # noqa: E402
from benchmark import run  # noqa: E402


def main(argv=None, dtype="bfloat16") -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--faults", default=",".join(reference.FAULTS))
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.check_device(cell["chips"])
    driver = run.load_module("drivers", cell["mix"]["driver"])
    m, seed = cell["config"], args.seed % 2**31
    model, _ = driver.build_model(driver.model_config(m, dtype), seed)
    over = {}
    for fault in [None] + [f for f in args.faults.split(",") if f]:
        readings = driver.control(m, model, seed, dtype, fault=fault)
        name = fault or "control"
        for what, reading, limit in readings:
            run.say(f"faults: {name}: {what}: {reading:.3e} (limit {limit:g})")
        over[name] = [what for what, reading, limit in readings
                      if not reading < limit]
    print(json.dumps({"seed": args.seed, "limits": driver.LIMITS[dtype],
                      "over": over,
                      "passes": [n for n, o in over.items() if not o]}),
          flush=True)


if __name__ == "__main__":
    main()
