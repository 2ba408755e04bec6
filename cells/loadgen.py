"""One general traffic generator and the arithmetic on what came back.

A traffic mix is a data file (``traffic/<name>.json``); this module turns
it and ``--seed`` into requests, sends them over HTTP to the ``serve``
proxy from the driving process, and reduces the timings.  It never imports
``jax`` or ``ray_tpu``.

Every seed gets the same work: the request sizes and the gaps between
arrivals are drawn once from the file's own ``pool_seed``, and ``--seed``
only decides their order and the token ids.  Where the file gives
``order.block``, the order changes only inside consecutive blocks of that
many requests, so every seed also sees the same bursts and lulls at the
same times: runs then differ by what the system does, not by when the load
came.  (Six runs of a fully shuffled 75-request window spread their TTFT
p95 by 16%; PERF.md section 6.)

The arrival clock and the latency-from-due-time rule follow
``benchmarks/serving_bench.py --mode openloop``; the percentile is
``ray_tpu/util/slo.py``'s nearest-rank ``quantile``, copied.
"""

import http.client
import json
import math
import threading
import time

import numpy as np


# ------------------------------------------------------------ arithmetic

def quantile(values, q: float) -> float:
    """Nearest-rank quantile, no interpolation: the p95 of 200 samples is
    the 190th smallest."""
    if not values:
        return math.nan
    s = sorted(values)
    rank = max(1, math.ceil(q * len(s)))
    return s[min(rank, len(s)) - 1]


def ttft_ms(rec) -> float:
    """First token received minus the time the request was DUE: a stall
    that delays the sender counts against the system, not for it."""
    return (rec["t_first"] - rec["due"]) * 1e3


def tpot_ms(rec) -> float:
    """(last token - first token) / (tokens - 1), per request."""
    return (rec["t_last"] - rec["t_first"]) / (rec["n_out"] - 1) * 1e3


# ------------------------------------------------------------ generation

def _draw(spec, rng, n):
    """n whole numbers from a distribution spec of the traffic file."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _gaps(arrivals, rng, n):
    """n gaps between arrivals, mean 1/rate."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return rng.exponential(1.0 / float(arrivals["rate_rps"]), n)


def _order(rng, n, block):
    """A permutation of range(n): whole, or inside blocks of ``block``."""
    if not block:
        return rng.permutation(n)
    out = np.arange(n)
    for lo in range(0, n, block):
        out[lo:lo + block] = rng.permutation(out[lo:lo + block])
    return out


def make_requests(traffic: dict, seed: int, vocab: int, horizon_s: float):
    """The requests of one run, in sending order.

    Open loop: as many as the file's rate puts into ``horizon_s``, each
    with its due time.  Closed loop: ``pool_size`` requests, no due time;
    clients take the next one when their last completes.
    """
    pool = np.random.default_rng(traffic["pool_seed"])
    order = np.random.default_rng(seed)
    open_loop = traffic["loop"] == "open"
    if open_loop:
        # a third more than the rate puts into the horizon: the drawn gaps
        # may sum to less, and the sender stops at the horizon anyway
        n = 16 + int(traffic["arrivals"]["rate_rps"] * horizon_s * 4 / 3)
    else:
        n = int(traffic["pool_size"])
    prompt_len = _draw(traffic["prompt_tokens"], pool, n)
    out_len = _draw(traffic["output_tokens"], pool, n)
    block = (traffic.get("order") or {}).get("block")
    sizes = _order(order, n, block)
    prompt_len, out_len = prompt_len[sizes], out_len[sizes]
    due = None
    if open_loop:
        gaps = _gaps(traffic["arrivals"], pool, n)
        due = np.cumsum(gaps[_order(order, n, block)])
    reqs = []
    for i in range(n):
        toks = order.integers(0, vocab, int(prompt_len[i]))
        reqs.append({"i": i, "prompt": toks.tolist(),
                     "max_tokens": int(out_len[i]),
                     "due": float(due[i]) if open_loop else None})
    return reqs


# ------------------------------------------------------------ one request

def send(host, port, path, req, stream: bool, timeout: float, clock):
    """POST one request; returns its record.  ``ok`` only for a 200 that
    carried exactly the tokens asked for."""
    rec = {"i": req["i"], "due": req.get("due_abs"), "sent": clock(),
           "t_first": None, "t_last": None, "t_done": None, "n_out": 0,
           "asked": req["max_tokens"], "n_prompt": len(req["prompt"]),
           "ok": False, "error": None, "ids": None}
    if rec["due"] is None:
        rec["due"] = rec["sent"]
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "temperature": 0.0})
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", path + ("?stream=1&method=stream" if stream
                                     else ""), body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"{resp.status} {resp.read()[:300]!r}"
            return rec
        if stream:
            done = None
            for line in resp:
                if not line.startswith(b"data: "):
                    continue
                now = clock()
                ev = json.loads(line[6:])
                if "token_id" in ev:
                    if rec["t_first"] is None:
                        rec["t_first"] = now
                    rec["t_last"] = now
                elif ev.get("done"):
                    done = ev
                elif "error" in ev:
                    rec["error"] = str(ev)[:300]
            if done is None:
                rec["error"] = rec["error"] or "stream ended without done"
                return rec
        else:
            done = json.loads(resp.read())
        rec["t_done"] = clock()
        rec["n_out"] = int(done.get("num_generated_tokens", 0))
        rec["ids"] = [int(w) for w in done.get("generated_text", "").split()]
        rec["ok"] = (rec["n_out"] == req["max_tokens"]
                     and len(rec["ids"]) == rec["n_out"])
        if not rec["ok"]:
            rec["error"] = (f"asked {req['max_tokens']} tokens, got "
                            f"{rec['n_out']} ({len(rec['ids'])} ids)")
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["error"] = repr(e)
    finally:
        conn.close()
    return rec


# ------------------------------------------------------------ load loops

class Load:
    """Sends a run's requests and keeps every record.

    Open loop: one scheduler thread sleeps to each due time and hands the
    request to a thread of its own, whatever the server is doing.  Closed
    loop: ``clients`` threads each send their next request when the last
    one has come back.  ``t0`` (monotonic) is the instant the schedule's
    zero falls on; records carry absolute monotonic times.
    """

    def __init__(self, traffic, reqs, host, port, path, timeout=180.0):
        self.traffic, self.reqs = traffic, reqs
        self.addr = (host, port, path)
        self.timeout = timeout
        self.records, self._lock = [], threading.Lock()
        self._threads, self._stop = [], threading.Event()
        self._next = 0
        self.t0 = None

    def _one(self, req):
        rec = send(*self.addr, req, self.traffic["stream"], self.timeout,
                   time.monotonic)
        with self._lock:
            self.records.append(rec)

    def _open(self, until_s):
        for req in self.reqs:
            if req["due"] > until_s or self._stop.is_set():
                break
            req["due_abs"] = self.t0 + req["due"]
            delay = req["due_abs"] - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                break
            t = threading.Thread(target=self._one, args=(req,), daemon=True)
            t.start()
            self._threads.append(t)

    def _client(self):
        while not self._stop.is_set():
            with self._lock:
                if self._next >= len(self.reqs):
                    return
                req = self.reqs[self._next]
                self._next += 1
            self._one(req)

    def start(self, until_s: float):
        """Begin sending; arrivals stop at ``until_s`` after ``t0``."""
        self.t0 = time.monotonic()
        if self.traffic["loop"] == "open":
            main = threading.Thread(target=self._open, args=(until_s,),
                                    daemon=True)
            main.start()
            self._main = [main]
        else:
            self._main = [threading.Thread(target=self._client, daemon=True)
                          for _ in range(int(self.traffic["clients"]))]
            for t in self._main:
                t.start()

    def stop(self):
        """No new request is sent after this; in-flight ones finish."""
        self._stop.set()

    def join(self, timeout: float) -> bool:
        """Wait for every sender; False if one is still out."""
        deadline = time.monotonic() + timeout
        for t in self._main + self._threads:
            t.join(max(0.0, deadline - time.monotonic()))
        return not any(t.is_alive() for t in self._main + self._threads)

    def snapshot(self):
        with self._lock:
            return list(self.records)


def norm_latency_ms(rec) -> float:
    """(last token - the time the request was DUE) / tokens: the whole of
    a request's latency, queueing and prefill included, a token."""
    return (rec["t_last"] - rec["due"]) / rec["n_out"] * 1e3


def tokens_in_window(rec, w0: float, w1: float) -> float:
    """A completed request's output tokens, pro-rated by the share of its
    time in the system (sent to done) that lies inside [w0, w1).  A client
    that does not stream cannot see when each token was made; counting a
    request whole at its completion lets a few 512-token answers that end
    just inside or outside the window move the count by several per cent
    (7.5% between runs, PERF.md section 6)."""
    inside = min(rec["t_done"], w1) - max(rec["sent"], w0)
    if inside <= 0:
        return 0.0
    return rec["n_out"] * inside / (rec["t_done"] - rec["sent"])


def reduce_window(records, traffic, w0: float, w1: float):
    """End-to-end numbers of the window [w0, w1) (monotonic seconds).

    Open loop: the requests DUE in the window, however late they finish;
    one that failed, or never produced a token, misses every latency and
    counts in ``failed``.  Closed loop: ``attempted`` is the requests that
    COMPLETED in the window, and ``output_tokens`` every completed
    request's tokens pro-rated to the window (``tokens_in_window``), so
    the records must include the requests that were in flight at ``w1``.
    """
    out = {}
    if traffic["loop"] == "open":
        mine = [r for r in records if w0 <= r["due"] < w1]
        good = [r for r in mine if r["ok"] and r["t_first"] is not None]
        out["attempted"], out["failed"] = len(mine), len(mine) - len(good)
        if traffic["stream"]:
            out["ttft_ms"] = [ttft_ms(r) for r in good]
            out["tpot_ms"] = [tpot_ms(r) for r in good if r["n_out"] > 1]
            out["norm_latency_ms"] = [norm_latency_ms(r) for r in good]
        out["lag_ms"] = [(r["sent"] - r["due"]) * 1e3 for r in mine]
        out["output_tokens"] = sum(r["n_out"] for r in good)
    else:
        mine = [r for r in records
                if r["t_done"] is not None and w0 <= r["t_done"] < w1
                or r["t_done"] is None and w0 <= r["sent"] < w1]
        good = [r for r in mine if r["ok"]]
        out["attempted"], out["failed"] = len(mine), len(mine) - len(good)
        out["output_tokens"] = sum(
            tokens_in_window(r, w0, w1) for r in records if r["ok"])
        out["output_tokens_at_completion"] = sum(r["n_out"] for r in good)
    out["good"] = good
    return out
