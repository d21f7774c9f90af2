"""Query generator ``perturbed_rows``: kNN queries that live near the data
(a corpus row plus Gaussian noise, on the corpus's 1/128 grid), all
distinct. Copied from ``chip_smoke.query_vectors`` (PR 21).

Every client draws from a stream of its own, seeded from ``--seed`` and the
client's number, so the queries of a run do not depend on how the clients
interleave. Parameters: ``field``, ``noise``, ``body``, the request body
(its ``knn`` clause gets ``field`` and ``query_vector`` filled in), and
``blocker_ks`` for the warm-up: a lone scan is over before a group can
arrive behind it, but the first request at a ``k`` whose power-of-two
bucket no program serves yet holds its dispatcher for as long as that
program takes to compile or load. Each ``k`` in the list is spent on one
blocker, so the list bounds what the warm-up loads beyond the mix's own
shapes.
"""

from __future__ import annotations

import copy
import json

import numpy as np

CHUNK = 256


class Queries:
    def __init__(self, params: dict, data: dict, seed: int, clients: int):
        self.params = params
        self.vecs = data["vector_fields"][params["field"]]
        self.rngs = [np.random.default_rng([int(seed), 2, c])
                     for c in range(clients)]
        self.warm_rng = np.random.default_rng([int(seed), 5])
        self.body = copy.deepcopy(params["body"])
        self.body["knn"]["field"] = params["field"]
        self.body["knn"]["query_vector"] = "@"
        self.head, self.tail = self._template(self.body)
        self.blocker_ks = list(params.get("blocker_ks", []))

    @staticmethod
    def _template(body: dict) -> tuple:
        head, tail = json.dumps(body).split('"@"')
        return head.encode(), tail.encode()

    def more(self, client: int) -> list:
        """The next requests of ``client``: (body bytes, query record)."""
        return self._draw(self.rngs[client], CHUNK)

    def warmup(self, n: int) -> list:
        """``n`` fresh requests for the warm-up, from a stream of their
        own."""
        return self._draw(self.warm_rng, n)

    def warmup_groups(self, bucket: int) -> list:
        """One group: every kNN request of this mix has the same shape."""
        return [self.warmup(bucket)]

    def blockers(self, n: int) -> list:
        """Up to ``n`` single requests, each at the next unspent ``k`` of
        ``blocker_ks``; none once the list is spent."""
        out = []
        for k in self.blocker_ks[:n]:
            body = copy.deepcopy(self.body)
            body["knn"]["k"] = k
            out += self._draw(self.warm_rng, 1, self._template(body))
        del self.blocker_ks[:n]
        return out

    def _draw(self, rng, n: int, template=None) -> list:
        head, tail = template or (self.head, self.tail)
        rows = self.vecs[rng.integers(0, self.vecs.shape[0], n)]
        q = rows + np.float32(self.params["noise"]) \
            * rng.standard_normal(rows.shape, np.float32)
        q = np.round(q * np.float32(128.0)) / np.float32(128.0)
        return [(head + json.dumps(v.tolist()).encode() + tail,
                 {"vector": v}) for v in q]

