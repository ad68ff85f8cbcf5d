"""Heap files: a table is a sequence of fixed-size pages in one file on disk."""
from __future__ import annotations

import json
import os

import numpy as np

from repro import obs
from repro.db.page import PageLayout, build_pages


FORMAT_VERSION = 2  # v2: MAXALIGN-unit line pointers, u32 tuple length
IOV_MAX = 1024  # the most buffers one vectored read takes (Linux, macOS)


class HeapFile:
    """Page-addressable heap file. Pages are read on demand (the buffer pool
    sits on top); ``read_pages`` is the device-handoff granularity."""

    def __init__(self, path: str):
        self.path = path
        with open(path + ".meta") as f:
            meta = json.load(f)
        if meta.get("format", 1) != FORMAT_VERSION:
            raise ValueError(
                f"{path}: heap format v{meta.get('format', 1)} != "
                f"v{FORMAT_VERSION}; rebuild the table"
            )
        self.layout = PageLayout(
            n_features=meta["n_features"],
            page_bytes=meta["page_bytes"],
            quantized=meta["quantized"],
        )
        self.n_tuples = meta["n_tuples"]
        self.n_pages = meta["n_pages"]

    def read_page(self, page_id: int) -> np.ndarray:
        return self.read_pages(np.array([page_id]))[0]

    def read_pages(self, page_ids: np.ndarray, out=None):
        """Reads page ``page_ids[k]`` into ``out[k]`` and returns ``out``: an
        (n, page_words) uint32 array (allocated when None) or a list of n
        page rows, such as the rows of a larger batch.

        Each maximal run of consecutive ids, in the order given, is one
        ``os.preadv`` straight into its rows, split every ``IOV_MAX`` pages;
        scattered ids cost a read each. The ``heap.read`` span counts pages,
        bytes and ``reads``, the read calls issued."""
        ids = np.asarray(page_ids, dtype=np.int64).reshape(-1)
        if out is None:
            out = np.empty((len(ids), self.layout.page_words), dtype=np.uint32)
        pb = self.layout.page_bytes
        breaks = (np.flatnonzero(np.diff(ids) != 1) + 1).tolist()
        with obs.span("heap.read", pages=len(ids), bytes=len(ids) * pb,
                      reads=0) as rec:
            fd = os.open(self.path, os.O_RDONLY)
            try:
                for s, e in zip([0, *breaks], [*breaks, len(ids)]):
                    for a in range(s, e, IOV_MAX):
                        rec.reads += self._preadv(fd, out[a:min(a + IOV_MAX, e)],
                                                  int(ids[a]) * pb)
            finally:
                os.close(fd)
        return out

    def _preadv(self, fd: int, rows, offset: int) -> int:
        """Fills ``rows`` from ``offset`` on, continuing after short reads;
        returns the number of read calls."""
        bufs = [memoryview(r).cast("B") for r in rows]
        left = sum(len(b) for b in bufs)
        calls = 0
        while True:
            got = os.preadv(fd, bufs, offset)
            calls += 1
            left -= got
            if left == 0:
                return calls
            if got == 0:
                raise EOFError(f"{self.path}: read past the end at byte {offset}")
            offset += got
            while got >= len(bufs[0]):  # drop the buffers filled
                got -= len(bufs.pop(0))
            bufs[0] = bufs[0][got:]

    def read_all(self) -> np.ndarray:
        data = np.fromfile(self.path, dtype=np.uint32)
        return data.reshape(self.n_pages, self.layout.page_words)


def write_table(
    path: str,
    features: np.ndarray,
    labels: np.ndarray,
    page_bytes: int = 32 * 1024,
    quantized: bool = False,
) -> HeapFile:
    """Materialize a training table as a heap file + sidecar metadata."""
    layout = PageLayout(
        n_features=features.shape[1], page_bytes=page_bytes, quantized=quantized
    )
    pages = build_pages(features, labels, layout)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    pages.tofile(tmp)
    os.replace(tmp, path)
    with open(path + ".meta", "w") as f:
        json.dump(
            {
                "format": FORMAT_VERSION,
                "n_features": layout.n_features,
                "page_bytes": layout.page_bytes,
                "quantized": layout.quantized,
                "n_tuples": int(features.shape[0]),
                "n_pages": int(pages.shape[0]),
            },
            f,
        )
    return HeapFile(path)


def write_token_table(
    path: str,
    seqs: list,
    page_bytes: int = 32 * 1024,
    width: int | None = None,
) -> HeapFile:
    """Materialize token sequences as a heap table the strider can decode.

    Each tuple's feature payload is its int32 token ids stored as raw words
    (float32 view — the strider streams bits, not values), right-padded with
    zeros to ``width``; the label column records the true sequence length.
    This is the table format LM PREDICT queries score from.
    """
    if not seqs:
        raise ValueError("token table needs at least one sequence")
    width = width or max(len(s) for s in seqs)
    if width <= 0:
        raise ValueError("token table width must be positive")
    feats = np.zeros((len(seqs), width), dtype=np.int32)
    for i, s in enumerate(seqs):
        if len(s) > width:
            raise ValueError(f"sequence {i} longer than table width {width}")
        feats[i, : len(s)] = np.asarray(s, dtype=np.int32)
    labels = np.array([len(s) for s in seqs], dtype=np.float32)
    return write_table(
        path, feats.view(np.float32), labels, page_bytes=page_bytes
    )
