"""A node child with the timed path broken underneath: an answer altered
where it is produced. The micro-batcher's result fan-out hands every
request the hits of its dispatch; here the best hit of each answer is
replaced by another document before the response is built. Used by
``test_rehearsal.py`` to see ``correct`` come out false."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import node_main  # noqa: E402


def main() -> int:
    from elasticsearch_tpu.search import microbatch
    original = microbatch.PlaneMicroBatcher._result

    def altered(slot):
        vals, hits, total = original(slot)
        hits = list(hits)
        if hits:
            shard, doc = hits[0]
            hits[0] = (shard, doc + 1 if doc + 1 < 2000 else doc - 1)
        return vals, hits, total

    microbatch.PlaneMicroBatcher._result = staticmethod(altered)
    return node_main.main()


if __name__ == "__main__":
    raise SystemExit(main())
