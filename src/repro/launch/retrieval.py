"""Batched associative-lookup server: request queue -> bucketed top-k search.

The retrieval twin of launch/serve.py's continuous-batching loop: lookup
requests (one binary code each, per-request k) arrive in a queue; the
shared ``BucketedBatchServer`` scheduler drains them in fixed query-batch
buckets (bounded compiled shapes, tail padding only on the final partial
bucket), runs one fused ``CAMIndex.search`` per bucket, then retires
every request with its slice of the batch result. Requests keep arriving
while batches run — submit/run can interleave.

CLI (self-contained demo: plants queries that must retrieve their source
row, then reports QPS and emulated PPAC cycles):

    PYTHONPATH=src python -m repro.launch.retrieval \
        --m 65536 --bits 256 --requests 256 --k 4 [--backend mxu]
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import numpy as np

from ..core.backend import use_compile_cache
from ..core.ppac import PPACConfig
from ..retrieval.index import CAMIndex
from .bucketed import BucketedBatchServer


@dataclasses.dataclass
class LookupRequest:
    rid: int
    code: np.ndarray                      # [n_bits] {0,1}
    k: int = 1
    scores: Optional[np.ndarray] = None   # [k] filled at retirement
    ids: Optional[np.ndarray] = None
    done: bool = False


class RetrievalServer(BucketedBatchServer):
    """Bucketed batch scheduler over one CAMIndex."""

    def __init__(self, index: CAMIndex, *, max_k: int = 16,
                 buckets=(1, 4, 16, 64), mesh=None, shard_axis: str = "data"):
        super().__init__(buckets=buckets)
        self.index = index
        self.max_k = max_k
        self.mesh = mesh
        self.shard_axis = shard_axis

    def _validate(self, req: LookupRequest):
        assert 1 <= req.k <= self.max_k, (req.k, self.max_k)
        assert req.code.shape == (self.index.n_bits,), req.code.shape

    def _row(self, req: LookupRequest) -> np.ndarray:
        return req.code

    def _run(self, codes: np.ndarray):
        return self.index.search(codes, k=self.max_k, mesh=self.mesh,
                                 shard_axis=self.shard_axis)

    def _retire(self, req: LookupRequest, res, i: int):
        req.scores = res.scores[i, : req.k].copy()
        req.ids = res.ids[i, : req.k].copy()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=65536)
    ap.add_argument("--bits", type=int, default=256)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--flip", type=int, default=8,
                    help="bits flipped between a planted query and its row")
    ap.add_argument("--metrics", action="store_true",
                    help="print the telemetry registry (Prometheus text) "
                         "after the run")
    args = ap.parse_args()
    use_compile_cache()
    serve_planted_lookups(args.m, args.bits, requests=args.requests,
                          k=args.k, backend=args.backend, flip=args.flip,
                          show_metrics=args.metrics)
    print("OK")


def serve_planted_lookups(m: int, bits: int, *, requests: int, k: int,
                          backend: str = "auto", flip: int = 8,
                          seed: int = 0, show_metrics: bool = False) -> dict:
    """Load ``m`` random ``bits``-wide codes, look up ``requests`` copies
    of planted rows with ``flip`` bits flipped, and assert recall@1 >=
    0.99. Returns the run's counts and host-clock seconds."""
    rng = np.random.default_rng(seed)
    index = CAMIndex(bits, config=PPACConfig(), backend=backend,
                     min_capacity=m)
    # bulk load random codes straight in packed form (bits = 32*W exactly)
    w = index.w
    if bits == 32 * w:
        index.add_packed(rng.integers(0, 2**32, (m, w), dtype=np.uint64)
                         .astype(np.uint32))
    else:
        index.add(rng.integers(0, 2, (m, bits)))

    server = RetrievalServer(index, max_k=k)
    targets = rng.integers(0, m, requests)
    from ..core.formats import unpack_bits

    db_bits = np.asarray(unpack_bits(index._codes[targets], bits))
    for i in range(requests):
        code = db_bits[i].copy()
        code[rng.choice(bits, size=flip, replace=False)] ^= 1
        server.submit(LookupRequest(i, code, k=k))

    cycles0 = index.counter.cycles
    t0 = time.perf_counter()
    done = server.run()
    dt = time.perf_counter() - t0
    cycles = index.counter.cycles - cycles0

    hits = sum(int(r.ids[0] == targets[r.rid]) for r in done)
    print(f"served {len(done)} lookups in {dt:.2f}s "
          f"({len(done) / dt:.1f} QPS, {server.batches} batches, "
          f"buckets={ {b: c for b, c in server.bucket_counts.items() if c} })")
    print(f"emulated PPAC cycles: {cycles} total, "
          f"{cycles / len(done):.1f}/query")
    print(f"recall@1 vs planted rows ({flip}/{bits} bits flipped): "
          f"{hits / len(done):.3f}")
    assert len(done) == requests, (len(done), requests)
    assert hits / len(done) >= 0.99, "planted neighbors must be retrieved"
    if show_metrics:
        print(server.metrics.prometheus_text(), end="")
    return dict(served=len(done), recall_at_1=hits / len(done), seconds=dt)


if __name__ == "__main__":
    main()
