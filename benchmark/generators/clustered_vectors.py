"""Data generator ``clustered_vectors``: a mixture of Gaussians standing in
for an embedding set (iid rows have no neighbourhoods for any index), on a
1/128 grid so that the JSON text, the engine's float32 and the reference's
float32 are the same numbers. Copied from ``chip_smoke.clustered_vectors``
(PR 21), with the generator seeded from ``--seed``.

Parameters (``data`` in the configuration file): ``docs``, ``dims``,
``docs_per_cluster``, ``spread``, ``field``.
"""

from __future__ import annotations

import numpy as np


def make(params: dict, seed: int) -> dict:
    n, dim = int(params["docs"]), int(params["dims"])
    rng = np.random.default_rng([int(seed), 1])
    n_centers = max(n // int(params["docs_per_cluster"]), 4)
    centers = rng.standard_normal((n_centers, dim), np.float32)
    out = np.empty((n, dim), np.float32)
    chunk = 1 << 17
    spread = np.float32(params["spread"])
    for lo in range(0, n, chunk):
        m = min(chunk, n - lo)
        out[lo: lo + m] = centers[rng.integers(0, n_centers, m)] \
            + spread * rng.standard_normal((m, dim), np.float32)
    np.round(out * np.float32(128.0), out=out)
    out /= np.float32(128.0)
    return {"n_docs": n, "text_fields": {},
            "vector_fields": {params["field"]: out}}
