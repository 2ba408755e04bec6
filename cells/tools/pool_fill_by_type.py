"""``pool_fill.py`` for a configuration whose engine keeps a pool a layer
type (``engine.num_blocks`` a dict by type): how many blocks of EACH pool a
serve cell's traffic holds live when every slot is busy.  Arithmetic on the
traffic file, no chip and no engine.

    python3 cells/tools/pool_fill_by_type.py <cell> [seed ...]

Slots are refilled in the generator's order at decode-window boundaries; a
slot holds its prompt, what it has generated and the window it is in.  A
type with a window holds a sequence's blocks from the first one a later
step can still see (``LLMEngine._release_behind_window``); the full type
holds them all, which is also what the window type would hold with no
release.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from cells import loadgen  # noqa: E402

DECODE_WINDOW = 16


def held(length, ahead, block, window):
    """Blocks a sequence of ``length`` cached positions holds with
    ``ahead`` more allocated for the decode window it is in."""
    first = 0 if window is None else max(0, length + 1 - window) // block
    return -(-(length + ahead) // block) - first


def blocks_over_time(reqs, slots, block, window):
    live, queue, out = [], list(reqs), []
    while queue:
        while len(live) < slots and queue:
            r = queue.pop(0)
            live.append([len(r["prompt"]), 0, r["max_tokens"]])
        out.append(sum(held(p + g, min(DECODE_WINDOW, n - g), block, window)
                       for p, g, n in live))
        live = [[p, g + DECODE_WINDOW, n] for p, g, n in live
                if g + DECODE_WINDOW < n]
    return out


def main():
    load = lambda *p: json.load(open(os.path.join(ROOT, *p)))  # noqa: E731
    cell = next(w for w in load("BENCHMARK.json")["workloads"]
                if w["name"] == sys.argv[1])
    config = load("cells", "configs", cell["config"] + ".json")
    engine, model = config["engine"], config["model"]
    traffic = dict(load("cells", "traffic", cell["traffic"] + ".json"),
                   loop="closed", pool_size=1536)
    windows = {"full": None, "window": model["sliding_window"]}
    for seed in [int(s) for s in sys.argv[2:]] or [0, 1, 2 ** 31 + 5]:
        reqs = loadgen.make_requests(traffic, seed, 32768, 0.0)
        for kind, window in windows.items():
            b = blocks_over_time(reqs, engine["batch_slots"],
                                 engine["block_size"], window)
            print(f"seed {seed} {kind}: {len(reqs)} requests, blocks held "
                  f"mean {sum(b) / len(b):.0f}, most {max(b)}, of "
                  f"{engine['num_blocks'][kind]}")


if __name__ == "__main__":
    main()
