"""python3 benchmark/sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 30

Finds the knee of an open-loop cell once: one engine, the cell's mix offered
at each rate in turn, and for each rate the backlog at the window's middle and
end. The knee is the highest rate at which the backlog at the end is no larger
than at the middle; the cell's file then fixes its rate at about four fifths
of it. Not part of a run of the benchmark: the rate of a cell is data.
"""
import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> None:
    from benchmark import arith, run
    from benchmark.drivers import serve
    from benchmark.tracing import Profile
    from benchmark.traffic import Traffic

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.check_device(cell["chips"])
    m = cell["config"]
    eng, _ = serve.build(m, args.seed)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell["mix"])
        mix["arrivals"]["rate_per_s"] = rate
        traffic = Traffic(mix, m["vocab_size"], args.seed)
        raw = serve.drive(eng, traffic, args.seconds, Profile(False),
                          run.say, first=100000 * (k + 1))
        eng.run()       # whatever was sent after the window
        ok = [r for r, n in raw["measured"] if r.done and not r.failed]
        ttft = [(r.prefill_time - r.arrival_time) * 1e3 for r in ok]
        e2e = serve.end_to_end(raw)
        print(json.dumps({
            "rate_per_s": rate, "sent": len(raw["measured"]),
            "unfinished": raw["unfinished"], "backlog_mid_end": raw["backlog"],
            "ttft_p50_ms": arith.median(ttft),
            "ttft_p90_ms": e2e.get("ttft_p90_ms"),
            "tpot_p90_ms": e2e.get("tpot_p90_ms"),
            "output_tok_s": e2e["output_tok_s"],
            "mixed_steps": raw["counters"]["prefill_chunks"],
            "steps": raw["counters"]["device_steps"]}), flush=True)


if __name__ == "__main__":
    sys.path[0] = os.path.dirname(HERE)
    main()
