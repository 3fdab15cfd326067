"""The load generator: one general generator that reads a traffic file.

A traffic file (benchmark/traffic/<traffic>.json) says how many clients,
which loop, which queries at which weights, how their literals are chosen,
how many executions warm a query up at most, and how many queries a traced
window holds.  The seed orders the queries; every seed gives the same
multiset of work per block, in another order."""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field

LOOPS = ("closed",)
LITERALS = ("validation",)


def check_traffic(traffic: dict) -> None:
    if traffic["loop"] not in LOOPS:
        raise ValueError(f"traffic loop {traffic['loop']!r}: this generator "
                         f"knows {LOOPS}")
    if traffic["literals"] not in LITERALS:
        raise ValueError(f"traffic literals {traffic['literals']!r}: this "
                         f"generator knows {LITERALS}")
    if traffic["clients"] < 1 or not traffic["queries"]:
        raise ValueError("traffic needs a client and a query")


def sequence(traffic: dict, seed: int, client: int):
    """Endless query names for one client: blocks that hold each query
    ``weight`` times, each block shuffled from (seed, client, block)."""
    block = [q["query"] for q in traffic["queries"]
             for _ in range(int(q["weight"]))]
    n = 0
    while True:
        order = list(block)
        random.Random(f"{seed}/{client}/{n}").shuffle(order)
        yield from order
        n += 1


@dataclass
class Sample:
    query: str
    seconds: float          # POST to last page, on the client's clock
    answer: list | None     # decoded rows; None when the query failed
    error: str | None = None


@dataclass
class Window:
    """Everything one measured window produced."""

    samples: list = field(default_factory=list)
    seconds: float = 0.0     # first POST to the last page of the last query

    @property
    def attempted(self) -> int:
        return len(self.samples)


def run_window(make_client, sqls: dict, traffic: dict, seed: int,
               seconds: float = 0.0, per_client: int = 0,
               around=None) -> Window:
    """Closed loop: each client sends its next query when the previous one's
    last page has arrived.  Runs until ``seconds`` have passed (a query in
    flight then is completed and counted, and the window is as long as it
    took) or, when ``per_client`` is given, for that many queries each.
    ``around(query)`` wraps each call (a traced run's client span)."""
    window = Window()
    lock = threading.Lock()
    t0 = time.perf_counter()

    def one_client(i: int) -> None:
        client = make_client()
        for n, q in enumerate(sequence(traffic, seed, i)):
            if per_client and n >= per_client:
                break
            if not per_client and time.perf_counter() - t0 >= seconds:
                break
            t = time.perf_counter()
            answer = error = None
            try:
                if around is None:
                    answer = client.execute(sqls[q])
                else:
                    with around(q):
                        answer = client.execute(sqls[q])
            except Exception as e:  # a failed query is a result, not a crash
                error = f"{type(e).__name__}: {e}"
            done = time.perf_counter()
            with lock:
                window.samples.append(Sample(q, done - t, answer, error))

    threads = [threading.Thread(target=one_client, args=(i,),
                                name=f"bench-client-{i}")
               for i in range(traffic["clients"])]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    window.seconds = time.perf_counter() - t0
    return window
