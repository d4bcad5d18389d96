"""The load generator: one general generator, driven by a traffic file.

Runs in a process of its own and never imports JAX, so it shares neither
the chip nor the interpreter lock with the server.  It reads a traffic
file (lengths, loop, rate, bursts), draws everything else from
``--seed``, offers the load to ``POST /generate`` and times every
streamed line on its own clock (``time.perf_counter``: CLOCK_MONOTONIC,
which the server's process shares).

    closed loop  "loop": "closed", "clients": n   each client sends its
                 next request when its stream ends
    open loop    "loop": "open", "rate_per_s": r, "arrivals": "poisson" |
                 "uniform", optional "burst": {"every_s": s, "size": k},
                 "max_in_flight": m; a request is timed from when it was
                 due, and the generator's lateness is reported

Every seed gets the same set of (prompt length, output length) pairs,
``schedule_size`` of them at the quantiles of the two distributions, in
another order, and token ids of its own.  Prints two JSON lines: the
plan (window start and end on the monotonic clock) at once, the result
when every request that was started has ended.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import random
import sys
import threading
import time

GOLDEN = 0.6180339887498949


def quantile_of(dist: dict, u: float) -> int:
    """The u-quantile (0 < u < 1) of a length distribution."""
    if dist["dist"] != "loguniform":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    lo, hi = dist["min"], dist["max"]
    return int(round(math.exp(math.log(lo) + u * math.log(hi / lo))))


def schedule(traffic: dict, seed: int) -> list:
    """[(prompt_len, output_len)] x schedule_size: the same multiset for
    every seed, shuffled by the seed."""
    n = traffic["schedule_size"]
    pairs = []
    for i in range(n):
        u = (i + 0.5) / n
        v = (i * GOLDEN + 0.5 / n) % 1.0   # decorrelated from u, fixed
        pairs.append((quantile_of(traffic["prompt_tokens"], u),
                      quantile_of(traffic["output_tokens"],
                                  min(max(v, 1e-9), 1 - 1e-9))))
    random.Random(seed).shuffle(pairs)
    return pairs


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> list:
    """Token ids of request ``index``: uniform over the vocabulary."""
    rng = random.Random(f"{seed}/{index}")
    return [rng.randrange(vocab) for _ in range(length)]


class Record:
    __slots__ = ("index", "due", "start", "prompt_len", "asked", "status",
                 "times", "tokens", "done", "error")

    def __init__(self, index, due, prompt_len, asked):
        self.index, self.due = index, due
        self.prompt_len, self.asked = prompt_len, asked
        self.start = None
        self.status = None
        self.times: list = []
        self.tokens: list = []
        self.done = None
        self.error = None

    def ok(self) -> bool:
        return (self.status == 200 and self.done == "max_tokens"
                and len(self.tokens) == self.asked)


def send(conn_box: list, host, port, rec: Record, body: bytes) -> None:
    """One request over a keep-alive connection; every streamed line is
    stamped as it is read."""
    rec.start = time.perf_counter()
    try:
        if conn_box[0] is None:
            conn_box[0] = http.client.HTTPConnection(host, port, timeout=180)
        conn = conn_box[0]
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec.status = resp.status
        if resp.status != 200:
            rec.error = resp.read()[:200].decode("utf-8", "replace")
            return
        for line in iter(resp.readline, b""):
            now = time.perf_counter()
            row = json.loads(line)
            if "done" in row:
                rec.done = row["done"]
                break
            rec.times.append(now)
            rec.tokens.append(row["token"])
        resp.read()
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"
        if conn_box[0] is not None:
            conn_box[0].close()
        conn_box[0] = None


def body_of(seed, index, plen, olen, vocab) -> bytes:
    """Every request is greedy: the served tokens can then be held against
    the reference's best."""
    return json.dumps({
        "tokens": prompt_ids(seed, index, plen, vocab),
        "max_new_tokens": olen, "temperature": 0.0, "seed": index,
    }).encode()


def run_closed(traffic, seed, host, port, vocab, t_stop, records, lock):
    pairs = schedule(traffic, seed)
    n_clients = traffic["clients"]

    def client(c):
        box = [None]
        k = c
        while time.perf_counter() < t_stop:
            plen, olen = pairs[k % len(pairs)]
            rec = Record(k, None, plen, olen)
            body = body_of(seed, k, plen, olen, vocab)
            send(box, host, port, rec, body)
            with lock:
                records.append(rec)
            k += n_clients
        if box[0] is not None:
            box[0].close()

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    return threads


def arrival_times(traffic, seed, t_begin, t_stop) -> list:
    rng = random.Random(f"{seed}/arrivals")
    rate = traffic["rate_per_s"]
    out, t = [], t_begin
    while t < t_stop:
        out.append(t)
        t += (rng.expovariate(rate) if traffic.get("arrivals", "poisson")
              == "poisson" else 1.0 / rate)
    burst = traffic.get("burst")
    if burst:
        t = t_begin + burst["every_s"]
        while t < t_stop:
            out.extend([t] * burst["size"])
            t += burst["every_s"]
    return sorted(out)


def run_open(traffic, seed, host, port, vocab, t_begin, t_stop, records,
             lock):
    pairs = schedule(traffic, seed)
    due = arrival_times(traffic, seed, t_begin, t_stop)
    nxt = [0]

    def worker():
        box = [None]
        while True:
            with lock:
                k = nxt[0]
                if k >= len(due):
                    break
                nxt[0] += 1
            delay = due[k] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            plen, olen = pairs[k % len(pairs)]
            rec = Record(k, due[k], plen, olen)
            send(box, host, port, rec,
                 body_of(seed, k, plen, olen, vocab))
            with lock:
                records.append(rec)
        if box[0] is not None:
            box[0].close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(traffic["max_in_flight"])]
    for t in threads:
        t.start()
    return threads


def reduce(records, traffic, seed, vocab, t0, t1) -> dict:
    """The window's numbers, from every request's own stamps."""
    inf = float("inf")
    tokens_in = sum(1 for r in records for t in r.times if t0 <= t <= t1)
    started = [r for r in records
               if r.start is not None and t0 <= (r.due or r.start) <= t1]
    ttfts = [(r.times[0] - (r.due or r.start)) if r.ok() and r.times else inf
             for r in started]
    gaps = [b - a for r in records for a, b in zip(r.times, r.times[1:])
            if t0 <= b <= t1]
    late = [r.start - r.due for r in records if r.due is not None]
    finished = [r for r in started if r.ok()]
    sample = []
    if finished:
        rng = random.Random(f"{seed}/sample")
        longest = max(finished, key=lambda r: r.prompt_len + r.asked)
        rest = [r for r in finished if r is not longest]
        rng.shuffle(rest)
        for r in [longest] + rest[: max(0, traffic["check_requests"] - 1)]:
            sample.append({
                "index": r.index,
                "prompt": prompt_ids(seed, r.index, r.prompt_len, vocab),
                "tokens": r.tokens})
    return {
        "event": "result", "t0": t0, "t1": t1,
        "tokens_in_window": tokens_in,
        "attempted": len(started),
        "failed": sum(1 for r in started if not r.ok()),
        "errors": [r.error or f"status {r.status} done {r.done} tokens "
                   f"{len(r.tokens)}/{r.asked}"
                   for r in records if not r.ok()][:5],
        "requests_total": len(records),
        "ttft_s": ttfts, "gap_s": gaps,
        "prompt_tokens_started": sum(r.prompt_len for r in started),
        "lateness_s": {"max": max(late), "mean": sum(late) / len(late)}
        if late else None,
        "sample": sample,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True, help="the traffic file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        traffic = json.load(f)

    begin = time.perf_counter() + 0.2
    t0 = begin + traffic["warm_seconds"]
    t1 = t0 + args.seconds
    print(json.dumps({"event": "plan", "t0": t0, "t1": t1}), flush=True)
    records: list = []
    lock = threading.Lock()
    time.sleep(max(0.0, begin - time.perf_counter()))
    if traffic["loop"] == "closed":
        threads = run_closed(traffic, args.seed, args.host, args.port,
                             args.vocab, t1, records, lock)
    elif traffic["loop"] == "open":
        threads = run_open(traffic, args.seed, args.host, args.port,
                           args.vocab, begin, t1, records, lock)
    else:
        raise SystemExit(f"unknown loop {traffic['loop']!r}")
    # An answer that comes late is late, not wrong: wait for each.
    give_up = t1 + traffic["drain_seconds"]
    for t in threads:
        t.join(max(0.0, give_up - time.perf_counter()))
    with lock:
        done = list(records)
    result = reduce(done, traffic, args.seed, args.vocab, t0, t1)
    # A stream that has not ended by now never came: it is a failure.
    never = sum(1 for t in threads if t.is_alive())
    result["attempted"] += never
    result["failed"] += never
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
