#!/usr/bin/env python3
"""Find the knee of an open-loop cell, once, on the chip: one server, the
cell's traffic at several rates one after the other.

    python3 benchmark/sweep.py --workload opt1.3b_chat --rates 2,3,4,5 --seconds 20

One JSON line per rate.  The knee is the highest rate at which the first and
the last third of the run have the same median time to first token (per
prompt token, since the thirds hold different lengths) and nothing is in
flight long after the close; the cell then runs at four fifths of it,
written into the traffic file with the sweep's lines."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args(argv)

    from benchmark import arith, harness, traffic
    from benchmark.drivers import serve
    spec = harness.Spec()
    cell = spec.cell(args.workload)
    cfg, tr = spec.config(cell), spec.traffic(cell)
    harness.device_gate(cell["chips"], False)
    harness.compile_cache()
    server = serve.Server(cfg, serve.make_params(cfg, args.seed))
    try:
        for rate in (float(x) for x in args.rates.split(",")):
            plan = traffic.open_loop(dict(tr, rate_rps=rate), args.seed,
                                     args.seconds, cfg["vocab_size"])
            t_open = time.perf_counter() + tr["lead_in_s"]
            disp, threads = serve.run_open_loop(server.port, plan, t_open,
                                                tr["request_timeout_s"])
            time.sleep(max(0.0, t_open + args.seconds - time.perf_counter()))
            in_flight = sum(1 for r in plan
                            if "sent" in r and "finished" not in r)
            disp.join()
            for th in threads:
                th.join(tr["request_timeout_s"])
            drain_s = time.perf_counter() - t_open - args.seconds
            meas = [r for r in plan if r["measured"] and r["token_times"]]
            ttft = [(r["token_times"][0] - r["due_abs"]) * 1e3 for r in meas]
            per_tok = [t / len(r["prompt"]) for t, r in zip(ttft, meas)]
            gaps = [g for r in meas
                    for g in arith.token_gaps_ms(r["token_times"])]
            third = max(1, len(meas) // 3)
            print(json.dumps({
                "rate_rps": rate, "requests": len(meas),
                "failed": sum(1 for r in plan if r.get("error")),
                "ttft_per_token_p50_ms_first_third":
                    arith.percentile(per_tok[:third], 50),
                "ttft_per_token_p50_ms_last_third":
                    arith.percentile(per_tok[-third:], 50),
                "ttft_per_token_p50_ms": arith.percentile(per_tok, 50),
                "ttft_ms_p95": arith.percentile(ttft, 95),
                "itl_p50_ms": arith.percentile(gaps, 50),
                "itl_p95_ms": arith.percentile(gaps, 95),
                "in_flight_at_close": in_flight, "drain_s": drain_s}),
                flush=True)
            time.sleep(1.0)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
