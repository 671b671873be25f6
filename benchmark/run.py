"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on. The
last line of stdout is the result. Everything that belongs to one cell is
found by name: `workloads/<traffic>.json` (the traffic mix; its `driver` names
`drivers/<driver>.py`), `configs/<config>.json` (the sizes) and, for the
`--trace 1` run, `readers/<metric>.py` for each per-layer metric of
BENCHMARK.json that lists the cell. A later PR adds files and entries and
edits none.
"""
import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import sys                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg: str) -> None:
    print(msg, flush=True)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by the name the data gives."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.py")
    if name.isidentifier():
        return importlib.import_module(f"benchmark.{kind}.{name}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell's entry of BENCHMARK.json with its mix and its sizes, and the
    metrics it reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json ({sorted(cells)})")
    cell = dict(cells[workload])
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        cell["config"] = json.load(f)
    with open(os.path.join(HERE, "workloads", cell["traffic"] + ".json")) as f:
        cell["mix"] = json.load(f)
    for group in ("end_to_end", "per_layer"):
        cell[group] = [x for x in bench[group]
                       if workload in x.get("workloads", [workload])]
    return cell


def check_device(chips: int) -> dict:
    """Fail unless the process sees `chips` TPU devices of a kind the peaks
    table knows; returns what the last line says of the device."""
    import jax

    from benchmark import arith

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no accelerator — platform is "
                         f"{devs[0].platform!r}, need 'tpu'")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, jax "
                         f"sees {len(devs)}")
    arith.peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             **driver_kw) -> dict:
    """One run; returns the result object of the last line."""
    from benchmark import arith, tracing

    device = check_device(cell["chips"])
    driver = load_module("drivers", cell["mix"]["driver"])
    res = driver.run(cell, seed, seconds, trace, say, **driver_kw)
    raw = res["raw"]
    setup_s = raw["t0"] - T_START
    device["memory_peak_bytes"] = memory_peak_bytes(cell["chips"])
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if not trace:
        values = dict(res["end_to_end"], setup_s=setup_s)
        for mt in cell["end_to_end"]:
            if mt["name"] not in values:
                raise SystemExit(f"benchmark: the run gave no "
                                 f"{mt['name']} (it has {sorted(values)})")
            out["metrics"][mt["name"]] = {"value": values[mt["name"]],
                                          "unit": mt["unit"]}
        return out
    red = tracing.reduce_events(res["profile"].events)
    say(f"trace: {red['window_s']:.3f} s traced, device busy "
        f"{red['busy_s']:.3f} s; host spans {red['host_spans']}; programs "
        f"{ {k: len(v) for k, v in red['programs'].items()} }; planes "
        f"{red['planes']}")
    ctx = dict(raw, config=cell["config"], mix=cell["mix"], trace=red,
               end_to_end=res["end_to_end"],
               peaks=arith.peaks(device["kind"]),
               memory_peak_bytes=device["memory_peak_bytes"])
    for mt in cell["per_layer"]:
        value = load_module("readers", mt["name"]).read(ctx)
        if value is not None:
            out["metrics"][mt["name"]] = {"value": value, "unit": mt["unit"]}
    device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    out["breakdown"] = {"device_ops": red["device_ops"],
                        "idle_gaps": red["idle_gaps"]}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    say(f"run: {args.workload} seed {args.seed}, window "
        f"{args.seconds:g} s, whole run {time.perf_counter() - T_START:.1f} s")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    # the package is imported as `benchmark`, from the checkout's root; this
    # file's own directory must not shadow the standard library
    sys.path[0] = ROOT
    main()
