"""Find the highest rate a serve cell's deployment sustains: its traffic
mix, open loop and streamed, at several rates on ONE deployment, a row of
numbers each.  A development aid, done once when a cell is defined (the
cell then offers a fixed rate); it reports no result.

    chiprun -- python3 cells/tools/sweep_rates.py <cell> 1.2,1.6,2.0 35

The knee is the rate from which the TTFT's median in the second half of
the run lies clearly above the whole run's: the queue is then growing.
"""

import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from cells import loadgen, run as cells_run, serve_runner  # noqa: E402


def sweep(sr, ctx, rates, seconds):
    for rate in rates:
        traffic = copy.deepcopy(sr.traffic)
        traffic.update(loop="open", stream=True,
                       arrivals={"process": "poisson", "rate_rps": rate})
        reqs = loadgen.make_requests(traffic, ctx["seed"],
                                     sr.model["vocab_size"], seconds)
        load = loadgen.Load(traffic, reqs, *sr.addr)
        load.start(until_s=seconds)
        time.sleep(seconds)
        st = sr.call("stats")
        drained = load.join(serve_runner.DRAIN_LIMIT_S)
        t_drain = time.monotonic() - load.t0 - seconds
        red = loadgen.reduce_window(load.snapshot(), traffic, load.t0,
                                    load.t0 + seconds)
        half = [loadgen.ttft_ms(r) for r in red["good"]
                if r["due"] - load.t0 > seconds / 2]
        norm = red["norm_latency_ms"]
        row = {"rate": rate, "attempted": red["attempted"],
               "failed": red["failed"],
               "ttft_p50": loadgen.quantile(red["ttft_ms"], 0.5),
               "ttft_p95": loadgen.quantile(red["ttft_ms"], 0.95),
               "ttft_p50_second_half": loadgen.quantile(half, 0.5),
               "tpot_p50": loadgen.quantile(red["tpot_ms"], 0.5),
               "tpot_p95": loadgen.quantile(red["tpot_ms"], 0.95),
               "norm_latency_mean": sum(norm) / max(1, len(norm)),
               "queued_at_end": st["queued"],
               "slots_at_end": st["slots_used"],
               "blocks_used_at_end": st["blocks_total"]
               - st["blocks_available"],
               "drain_s": t_drain, "drained": drained,
               "out_tokens_per_s": red["output_tokens"] / seconds}
        print("cells: sweep " + " ".join(
            f"{k}={v:.1f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in row.items()), flush=True)


def main():
    workload, rates = sys.argv[1], [float(r) for r in sys.argv[2].split(",")]
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else 35.0
    _, _, ctx = cells_run.prepare(workload, 0, seconds, 0, False)
    cluster = ctx["cluster"]
    try:
        cluster.start()
        sr = serve_runner.ServeRun(ctx)
        sr.deploy(False)
        sr.warm_up()
        sweep(sr, ctx, rates, seconds)
        return 0
    except BaseException as e:  # noqa: BLE001 - every path explains itself
        cluster.explain(e)
        return 1
    finally:
        cluster.stop()


if __name__ == "__main__":
    sys.exit(main())
