"""Write one shard's store the way a flushed index leaves it on disk, so
that a node started on the data directory recovers the segment when the
index is created (``Engine._recover_from_store``), as after a restart.

The layout mirrors ``elasticsearch_tpu/index/store.py`` ``save_segment`` and
``Engine.flush`` (one ``seg__0.npz``, its ``.live.npy`` sidecar and a
``commit_point.json`` under ``<data>/<index>/0/store``), written from plain
arrays: no ``Segment`` is constructed (that would upload to a device), and
this module imports only numpy. ``tests/test_store_layout.py`` holds the
layout against what a real flush writes.

Documents carry no ``_source`` (the stand-in corpora have none) and text
fields carry no positions (a match query reads none): the position offsets
are all zero, stored deflated so that they cost no disk.
"""

from __future__ import annotations

import io
import json
import os
import time
import zipfile

import numpy as np

FORMAT_VERSION = 2
SEG_ID = "_0"


def _pack_strs(strs) -> tuple:
    """list[str] -> (uint8 data, int64 offsets[len+1]), as store.pack_strs."""
    encoded = [s.encode("utf-8") for s in strs]
    offsets = np.zeros(len(encoded) + 1, np.int64)
    if encoded:
        np.cumsum([len(b) for b in encoded], out=offsets[1:])
    data = np.frombuffer(b"".join(encoded), dtype=np.uint8).copy() \
        if encoded else np.empty(0, np.uint8)
    return data, offsets


def _decimal_uids(n: int) -> tuple:
    """pack_strs of "0".."n-1" without a Python loop over documents."""
    ids = np.arange(n, dtype=np.int64)
    width = np.ones(n, np.int64)
    p = 10
    while p <= max(n - 1, 0):
        width += ids >= p
        p *= 10
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(width, out=offsets[1:])
    data = np.empty(int(offsets[-1]), np.uint8)
    # digit j (from the right) of every id that has it
    for j in range(int(width.max()) if n else 0):
        has = width > j
        pos = offsets[1:][has] - 1 - j
        data[pos] = (ids[has] // (10 ** j)) % 10 + ord("0")
    return data, offsets


def segment_arrays(n_docs: int, text_fields: dict, vector_fields: dict):
    """The arrays and manifest of one segment.

    ``text_fields``: name -> dict(terms=list[str], df i32[V], offsets
    i64[V+1], docs i32[P], tf f32[P], doc_len f32[N]);
    ``vector_fields``: name -> f32[N, d]."""
    arrays: dict = {}
    manifest = {"format": FORMAT_VERSION, "seg_id": SEG_ID,
                "n_docs": int(n_docs), "text_fields": [],
                "keyword_fields": [], "numeric_fields": [],
                "vector_fields": []}
    arrays["uids_data"], arrays["uids_off"] = _decimal_uids(n_docs)
    # every _source is JSON null: four bytes a document
    arrays["src_data"] = np.frombuffer(b"null" * n_docs, np.uint8).copy()
    arrays["src_off"] = np.arange(n_docs + 1, dtype=np.int64) * 4
    arrays["seq_nos"] = np.arange(n_docs, dtype=np.int64)
    arrays["versions"] = np.ones(n_docs, np.int64)
    arrays["routing_isnull"] = np.ones(n_docs, bool)
    arrays["routing_data"] = np.empty(0, np.uint8)
    arrays["routing_off"] = np.zeros(n_docs + 1, np.int64)
    for i, (name, f) in enumerate(sorted(text_fields.items())):
        doc_len = np.asarray(f["doc_len"], np.float32)
        manifest["text_fields"].append(
            {"name": name, "sum_dl": float(doc_len.sum(dtype=np.float64)),
             "field_doc_count": int((doc_len > 0).sum())})
        p = f"t{i}_"
        arrays[p + "terms_data"], arrays[p + "terms_off"] = \
            _pack_strs(f["terms"])
        tf = np.asarray(f["tf"], np.float32)
        offsets = np.asarray(f["offsets"], np.int64)
        arrays[p + "df"] = np.asarray(f["df"], np.int32)
        arrays[p + "offsets"] = offsets
        arrays[p + "docs"] = np.asarray(f["docs"], np.int32)
        arrays[p + "tf"] = tf
        arrays[p + "doc_len"] = doc_len
        ttf = np.add.reduceat(tf.astype(np.float64), offsets[:-1]) \
            if tf.size else np.zeros(offsets.size - 1)
        ttf[np.diff(offsets) == 0] = 0
        arrays[p + "ttf"] = ttf.astype(np.int64)
        arrays[p + "pos_off"] = np.zeros(tf.shape[0] + 1, np.int64)
        arrays[p + "pos_flat"] = np.empty(0, np.int32)
    for i, (name, mat) in enumerate(sorted(vector_fields.items())):
        manifest["vector_fields"].append({"name": name})
        p = f"v{i}_"
        arrays[p + "mat"] = np.ascontiguousarray(mat, np.float32)
        arrays[p + "exists"] = np.ones(n_docs, bool)
    arrays["manifest"] = np.frombuffer(
        json.dumps(manifest).encode("utf-8"), dtype=np.uint8).copy()
    return arrays


def _write_npz(path: str, arrays: dict) -> int:
    """An ``.npz`` that ``np.load`` reads: members stored as they are, but
    all-zero ones deflated (zipfile inflates them on read)."""
    with zipfile.ZipFile(path, "w", zipfile.ZIP_STORED,
                         allowZip64=True) as z:
        for name, a in arrays.items():
            deflate = a.size > 4096 and not a.any()
            info = zipfile.ZipInfo(name + ".npy")
            info.compress_type = zipfile.ZIP_DEFLATED if deflate \
                else zipfile.ZIP_STORED
            with z.open(info, "w", force_zip64=True) as fh:
                if deflate:
                    buf = io.BytesIO()
                    np.lib.format.write_array(buf, a, allow_pickle=False)
                    fh.write(buf.getvalue())
                else:
                    np.lib.format.write_array(fh, a, allow_pickle=False)
    return os.path.getsize(path)


def write_index_store(data_dir: str, index: str, mappings: dict,
                      n_docs: int, text_fields: dict,
                      vector_fields: dict) -> int:
    """One index, one shard, one segment under ``data_dir``; returns the
    bytes written."""
    store_dir = os.path.join(data_dir, index, "0", "store")
    os.makedirs(store_dir, exist_ok=True)
    arrays = segment_arrays(n_docs, text_fields, vector_fields)
    fname = f"seg_{SEG_ID}.npz"
    written = _write_npz(os.path.join(store_dir, fname), arrays)
    live_path = os.path.join(store_dir, f"seg_{SEG_ID}.live.npy")
    np.save(live_path, np.ones(n_docs, bool))
    commit = {"segments": [fname], "max_seq_no": n_docs - 1,
              "local_checkpoint": n_docs - 1, "primary_term": 1,
              "mapping": mappings, "timestamp": time.time(),
              "tombstones": {}}
    cp = os.path.join(store_dir, "commit_point.json")
    with open(cp, "w") as f:
        json.dump(commit, f)
    return written + os.path.getsize(live_path) + os.path.getsize(cp)
