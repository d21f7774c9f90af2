"""The store the benchmark writes against what a real flush leaves on
disk: same files, same arrays (names, dtypes, shapes), and the engine
recovers it and answers from it."""

import json
import os

import numpy as np

from conftest import CHECKOUT
from harness import store


def test_store_matches_a_real_flush(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import sys
    sys.path.insert(0, CHECKOUT)
    from elasticsearch_tpu.node.indices_service import IndicesService
    mappings = {"properties": {
        "body": {"type": "text"},
        "vec": {"type": "dense_vector", "dims": 4, "similarity": "cosine"}}}
    real = IndicesService(str(tmp_path / "real"))
    idx = real.create_index("t", {"number_of_shards": 1}, mappings)
    docs = [{"body": f"t{i % 3} t{i % 5} t1", "vec": [i, 1, 2, 3]}
            for i in range(20)]
    for i, d in enumerate(docs):
        idx.index_doc(str(i), d)
    idx.flush()
    real_dir = tmp_path / "real" / "t" / "0" / "store"
    with np.load(real_dir / "seg__0.npz") as z:
        real_arrays = {k: z[k] for k in z.files}
    # the same corpus as plain arrays
    terms = [f"t{i}" for i in range(5)]
    f = {k[3:]: real_arrays[k] for k in real_arrays if k.startswith("t0_")}
    text = {"body": dict(terms=terms, df=f["df"], offsets=f["offsets"],
                         docs=f["docs"], tf=f["tf"], doc_len=f["doc_len"])}
    vecs = {"vec": real_arrays["v0_mat"]}
    mine_dir = tmp_path / "mine"
    store.write_index_store(str(mine_dir), "t", mappings, 20, text, vecs)
    mine_store = mine_dir / "t" / "0" / "store"
    assert sorted(os.listdir(mine_store)) == sorted(os.listdir(real_dir))
    with np.load(mine_store / "seg__0.npz") as z:
        mine = {k: z[k] for k in z.files}
    assert set(mine) == set(real_arrays)
    for k, a in real_arrays.items():
        assert mine[k].dtype == a.dtype, k
        if k in ("src_data", "src_off", "manifest") \
                or k.endswith(("pos_off", "pos_flat")):
            continue        # no _source, no positions in the stand-in
        assert mine[k].shape == a.shape, k
        assert np.array_equal(mine[k], a), k
    rc = json.load(open(real_dir / "commit_point.json"))
    mc = json.load(open(mine_store / "commit_point.json"))
    assert set(rc) == set(mc)
    for k in ("segments", "max_seq_no", "local_checkpoint", "primary_term",
              "mapping", "tombstones"):
        assert rc[k] == mc[k], k
    # and the engine comes up holding the segment, as after a restart
    node = IndicesService(str(mine_dir))
    again = node.create_index("t", {"number_of_shards": 1}, mappings)
    assert again.count({}) == 20
    r = again.search({"query": {"match": {"body": "t1 t2"}}, "size": 3,
                      "_source": False})
    want = idx.search({"query": {"match": {"body": "t1 t2"}}, "size": 3,
                       "_source": False})
    assert r.total == want.total
    assert [h.doc_id for h in r.hits] == [h.doc_id for h in want.hits]
