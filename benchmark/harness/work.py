"""The least work an algorithm needs for one dispatch, from the cell's
shapes and the queries alone, and the least time a chip needs for it. These
read the same whatever implements the step; a roofline share is this least
time over the device time the trace shows.
"""

from __future__ import annotations

import json
import os


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in peaks.json: add "
            f"a sourced row, there is no default")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict) -> tuple:
    """(seconds, 'memory'|'compute'): the larger of bytes over the memory
    bandwidth and flops over the bf16 peak."""
    t_mem = work["bytes"] / (peaks["hbm_gb_per_s"] * 1e9)
    t_flop = work["flops"] / (peaks["bf16_tflop_per_s"] * 1e12)
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")


def knn_exact(n_docs: int, dims: int, stored_bytes: int, batch: int,
              k: int) -> dict:
    """Exact kNN over a resident corpus: one read of the corpus per
    dispatch, the queries in, the top-k (score + id) out."""
    return {"bytes": n_docs * dims * stored_bytes + batch * dims * 4
            + batch * k * 8,
            "flops": 2.0 * batch * n_docs * dims}


def roofline_share(works: list, step_ms: float, peaks: dict, say,
                   name: str) -> float:
    """Per cent: the mean least time of the dispatches' work over the step
    program's device time per execution; says which peak bounds it."""
    least = [least_seconds(w, peaks) for w in works]
    mean = sum(t for t, _ in least) / len(least)
    say(f"{name}: bound by {least[0][1]}, least {mean * 1e3:.4f} ms a "
        f"dispatch over {len(least)} dispatches")
    return 100.0 * mean / (step_ms / 1e3)
