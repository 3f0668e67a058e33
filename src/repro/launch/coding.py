"""Batched LDPC-decode server: request queue -> bucketed bit-flip decode.

The coding twin of launch/retrieval.py's continuous-batching loop: decode
requests (one noisy word each) arrive in a queue; the shared
``BucketedBatchServer`` scheduler drains them in fixed word-batch buckets
(bounded compiled shapes, tail padding only on the final partial bucket),
runs one fused ``BitFlipDecoder.decode`` per bucket, then retires every
request with its slice of the batch result.  With a ``mesh``, each
bucket's codeword block row-shards over the mesh axis — bit-identical to
single device.

CLI (self-contained demo: plants codewords pushed through a worst-case
t-error channel that the array code provably corrects, then reports QPS
and emulated PPAC cycles vs the §IV-B compute-cache baseline):

    PYTHONPATH=src python -m repro.launch.coding \
        --rows 32 --cols 32 --requests 256 [--errors 1] [--backend mxu]
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import numpy as np

from ..core.backend import use_compile_cache
from ..gf2.ldpc import BitFlipDecoder, LDPCCode, bsc_flip, make_array_ldpc
from .bucketed import BucketedBatchServer


@dataclasses.dataclass
class DecodeRequest:
    rid: int
    word: np.ndarray                      # [n] {0,1} noisy channel output
    msg: Optional[np.ndarray] = None      # [k] decoded message bits
    codeword: Optional[np.ndarray] = None
    ok: bool = False
    iters: int = -1
    done: bool = False


class CodingServer(BucketedBatchServer):
    """Bucketed batch scheduler over one BitFlipDecoder."""

    def __init__(self, decoder: BitFlipDecoder, *,
                 buckets=(1, 4, 16, 64), mesh=None, shard_axis: str = "data"):
        super().__init__(buckets=buckets)
        self.decoder = decoder
        self.mesh = mesh
        self.shard_axis = shard_axis

    @property
    def code(self) -> LDPCCode:
        return self.decoder.code

    def _validate(self, req: DecodeRequest):
        assert req.word.shape == (self.code.n,), req.word.shape

    def _row(self, req: DecodeRequest) -> np.ndarray:
        return req.word

    def _run(self, words: np.ndarray):
        return self.decoder.decode(words, mesh=self.mesh,
                                   shard_axis=self.shard_axis)

    def _retire(self, req: DecodeRequest, res, i: int):
        req.codeword = res.codewords[i].copy()
        req.msg = res.msgs[i].copy()
        req.ok = bool(res.ok[i])
        req.iters = int(res.iters[i])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--cols", type=int, default=32)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--errors", type=int, default=1,
                    help="bit errors planted per word (array code "
                         "guarantees correction of 1)")
    ap.add_argument("--max-iters", type=int, default=8)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--metrics", action="store_true",
                    help="print the telemetry registry (Prometheus text) "
                         "after the run")
    args = ap.parse_args()
    use_compile_cache()
    serve_noisy_words(args.rows, args.cols, requests=args.requests,
                      errors=args.errors, max_iters=args.max_iters,
                      backend=args.backend, show_metrics=args.metrics)
    print("OK")


def serve_noisy_words(rows: int, cols: int, *, requests: int,
                      errors: int = 1, max_iters: int = 8,
                      backend: str = "auto", seed: int = 0,
                      show_metrics: bool = False) -> dict:
    """Encode ``requests`` random messages with the rows x cols array
    code, flip ``errors`` bits per word and decode them through the
    server; asserts every message is recovered when ``errors`` is within
    the code's guarantee. Returns the run's counts, host seconds and the
    kernel backend the decoder resolved."""
    rng = np.random.default_rng(seed)
    code = make_array_ldpc(rows, cols)
    decoder = BitFlipDecoder(code, backend=backend, max_iters=max_iters)
    print(f"array code: n={code.n} k={code.k} rate={code.rate:.3f} "
          f"checks={code.n_chk} guaranteed_t={code.guaranteed_t}")

    msgs = rng.integers(0, 2, (requests, code.k)).astype(np.uint8)
    codewords = code.encode(msgs, backend=decoder.backend)
    noisy = bsc_flip(codewords, errors, rng)

    server = CodingServer(decoder)
    for i in range(requests):
        server.submit(DecodeRequest(i, noisy[i]))

    cycles0 = decoder.counter.cycles
    t0 = time.perf_counter()
    done = server.run()
    dt = time.perf_counter() - t0
    cycles = decoder.counter.cycles - cycles0

    recovered = sum(int(np.array_equal(r.msg, msgs[r.rid])) for r in done)
    print(f"served {len(done)} decodes in {dt:.2f}s "
          f"({len(done) / dt:.1f} QPS, {server.batches} batches, "
          f"buckets={ {b: c for b, c in server.bucket_counts.items() if c} })")
    print(f"emulated PPAC cycles: {cycles} total, "
          f"{cycles / len(done):.1f}/word; compute-cache baseline "
          f"{decoder.compute_cache_cycles_per_word_iteration()} cycles/word/iter "
          f"vs PPAC {decoder.cycles_per_word_iteration()}")
    print(f"recovered {recovered}/{len(done)} messages "
          f"({errors} bit errors/word)")
    assert len(done) == requests, (len(done), requests)
    if errors <= code.guaranteed_t:
        assert recovered == len(done), \
            "<= t errors must always be corrected"
    if show_metrics:
        print(server.metrics.prometheus_text(), end="")
    return dict(served=len(done), recovered=recovered, seconds=dt,
                backend=decoder.backend)


if __name__ == "__main__":
    main()
