"""Data-path benchmark: matching-based sequence packing quality + speed
(the second framework integration of the paper's technique)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.data import pack_documents, packing_efficiency


def run(scale: str = "small"):
    rows = []
    rng = np.random.default_rng(0)
    for n_docs, seq_len in ((256, 1024), (1024, 4096)):
        docs = [
            rng.integers(1, 50000, size=int(l)).astype(np.int32)
            for l in np.clip(rng.pareto(1.5, n_docs) * 256 + 16, 16, seq_len)
        ]
        t0 = time.perf_counter()
        rows_packed, mask = pack_documents(docs, n_docs // 2, seq_len)
        dt = time.perf_counter() - t0
        eff = packing_efficiency(mask)
        # baseline: one doc per row
        plain = np.zeros((n_docs // 2, seq_len), bool)
        for i in range(n_docs // 2):
            plain[i, : min(len(docs[i]), seq_len)] = True
        rows.append(emit(
            f"packing/docs{n_docs}_seq{seq_len}", dt,
            f"fill={eff:.3f};baseline={packing_efficiency(plain):.3f}"
        ))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
