"""Query generator ``mass_bags``: match queries that are bags of distinct
terms, each term drawn with probability proportional to its posting mass,
like words sampled from a query log (``bench.sample_queries``, PR 21): head
terms appear constantly.

The bags are drawn from ``--seed`` in plain random order and dealt to the
clients in turn, so a run's requests do not depend on how the clients
interleave. Bags are distinct as sets (an identical body would be served by
the request cache, not by the plane).

Parameters: ``field``, ``bag_terms`` (the bag sizes, cycled) and ``body``
(its ``query.match.<field>`` gets the bag).
"""

from __future__ import annotations

import copy
import json
import threading

import numpy as np

CHUNK = 64


class Queries:
    def __init__(self, params: dict, data: dict, seed: int, clients: int):
        field = data["text_fields"][params["field"]]
        df = np.asarray(field["df"], np.float64)
        self.eligible = np.flatnonzero(df >= 2)
        self.cdf = np.cumsum(df[self.eligible] / df[self.eligible].sum())
        self.rng = np.random.default_rng([int(seed), 2])
        self.warm_rng = np.random.default_rng([int(seed), 5])
        self.lock = threading.Lock()
        self.sizes = list(params["bag_terms"])
        self.seen: set = set()
        self.n_drawn = 0
        body = copy.deepcopy(params["body"])
        body["query"]["match"][params["field"]] = "@"
        head, tail = json.dumps(body).split('"@"')
        self.head, self.tail = head.encode(), tail.encode()
        self.queues = [[] for _ in range(clients)]

    def _requests(self, n: int, rng) -> list:
        out = []
        while len(out) < n:
            size = self.sizes[self.n_drawn % len(self.sizes)]
            self.n_drawn += 1
            for _ in range(64):
                draws = np.searchsorted(self.cdf, rng.random(size))
                bag = self.eligible[np.minimum(draws, self.eligible.size - 1)]
                key = tuple(sorted(set(bag.tolist())))
                if len(key) == size and key not in self.seen:
                    break
            else:
                raise RuntimeError("mass_bags: no fresh bag in 64 draws")
            self.seen.add(key)
            terms = [f"t{t}" for t in bag]
            out.append((self.head + json.dumps(" ".join(terms)).encode()
                        + self.tail, {"terms": terms}))
        return out

    def more(self, client: int) -> list:
        """The next requests of ``client``: (body bytes, query record).
        Whoever runs out first has the next chunk drawn for every client."""
        with self.lock:
            if not self.queues[client]:
                fresh = self._requests(CHUNK * len(self.queues), self.rng)
                for c, q in enumerate(self.queues):
                    q.extend(fresh[c::len(self.queues)])
            out, self.queues[client] = self.queues[client], []
        return out

    def warmup(self, n: int) -> list:
        """``n`` fresh requests for the warm-up, from a stream of their
        own: the window's bags do not depend on how long warm-up ran."""
        with self.lock:
            return self._requests(n, self.warm_rng)

    def warmup_groups(self, bucket: int) -> list:
        """One plain burst: which compiled shapes a batch of bags asks for
        is the engine's to say (its tiering rules), and the node offers no
        way to warm a named shape (PERF.md, Open questions)."""
        return [self.warmup(bucket)]

    def blockers(self, n: int) -> list:
        return []
