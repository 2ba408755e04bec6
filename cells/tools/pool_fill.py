"""How many blocks of the paged cache a serve cell's traffic holds live
when every slot is busy: arithmetic on the traffic file, no chip and no
engine.  A configuration's ``engine.num_blocks`` is sized from this (a
fifth over the peak), so that the pool is neither a reserve the traffic
never touches nor short enough to preempt a request.

    python3 cells/tools/pool_fill.py <cell> [seed ...]

Slots are refilled in the generator's order at decode-window boundaries; a
slot holds its prompt, what it has generated and the window it is in.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from cells import loadgen  # noqa: E402


def blocks_over_time(reqs, slots, block, window):
    live, queue, out = [], list(reqs), []
    while queue:
        while len(live) < slots and queue:
            r = queue.pop(0)
            live.append([len(r["prompt"]), 0, r["max_tokens"]])
        out.append(sum(-(-(p + min(g + window, n)) // block)
                       for p, g, n in live))
        live = [[p, g + window, n] for p, g, n in live if g + window < n]
    return out


def main():
    load = lambda *p: json.load(open(os.path.join(ROOT, *p)))  # noqa: E731
    cell = next(w for w in load("BENCHMARK.json")["workloads"]
                if w["name"] == sys.argv[1])
    engine = load("cells", "configs", cell["config"] + ".json")["engine"]
    traffic = dict(load("cells", "traffic", cell["traffic"] + ".json"),
                   loop="closed", pool_size=1536)
    for seed in [int(s) for s in sys.argv[2:]] or [0, 1, 2 ** 31 + 5]:
        reqs = loadgen.make_requests(traffic, seed, 32768, 0.0)
        b = blocks_over_time(reqs, engine["batch_slots"],
                             engine["block_size"], 16)
        print(f"seed {seed}: {len(reqs)} requests, blocks held mean "
              f"{sum(b) / len(b):.0f}, most {max(b)}, of "
              f"{engine.get('num_blocks')}")


if __name__ == "__main__":
    main()
