#!/usr/bin/env python
"""Measure the AG News host input pipeline against the device step rate.

The reference mitigates its collate-time tokenization cost with
DataLoader worker processes (--workers, resnet50_test.py:52,321-352;
transformer_test.py uses the same loaders).  Here the equivalent is
ParallelBatchIterator threads over the GIL-releasing C++ WordPiece core.
This script answers: does clean+tokenize+bucket at bs=256 keep up with
the transformer's step rate?  That rate is not measured on the chip
(no cell of BENCHMARK.json runs the encoder; PERF.md 7 row 0b): compare
with the ledger's once it has one.

No TPU needed — it measures the HOST side in isolation:
  * build a realistic corpus (AG News-like title+description lengths),
  * run the full encode path (WordPiece via the native core) through
    BatchLoader with 1..N workers,
  * report sustained examples/sec per worker count.

Run: python scripts/text_pipeline_bench.py [--n 24000] [--bs 256]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_corpus(n: int, seed: int = 0):
    """AG News-shaped raw text: ~40-60 space-separated words drawn from a
    Zipf-ish vocabulary, with some HTML/URL noise the cleaner must strip."""
    rng = np.random.default_rng(seed)
    vocab = [f"word{i}" for i in range(20000)]
    zipf = rng.zipf(1.3, size=(n, 60)) % len(vocab)
    samples = []
    for i in range(n):
        words = [vocab[j] for j in zipf[i, : rng.integers(35, 60)]]
        if i % 7 == 0:
            words.insert(0, "<b>Breaking</b>")
        if i % 11 == 0:
            words.append("http://example.com/story?id=%d" % i)
        samples.append((" ".join(words), int(rng.integers(0, 4))))
    return samples


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--n", type=int, default=24000)
    p.add_argument("--bs", type=int, default=256)
    p.add_argument("--max_len", type=int, default=256)
    p.add_argument("--workers", default="1,2,4,8")
    args = p.parse_args()

    from faster_distributed_training_tpu.data.agnews import AGNewsDataset
    from faster_distributed_training_tpu.data.loader import (
        BatchLoader, ParallelBatchIterator)
    from faster_distributed_training_tpu.runtime import native_lib

    t0 = time.monotonic()
    ds = AGNewsDataset.from_samples(build_corpus(args.n))
    print(f"dataset: {len(ds)} samples, tokenizer="
          f"{type(ds.tokenizer).__name__}, "
          f"native_core={native_lib.available()}, "
          f"build={time.monotonic() - t0:.1f}s")

    for w in [int(x) for x in args.workers.split(",")]:
        loader = BatchLoader(ds, args.bs, shuffle=True, max_len=args.max_len,
                             process_index=0, process_count=1)
        it = (ParallelBatchIterator(loader, w, depth=2 * w) if w > 1
              else loader)
        n_seen = 0
        t0 = time.monotonic()
        for batch in it:
            n_seen += batch["tokens"].shape[0]
        dt = time.monotonic() - t0
        print(f"workers={w}: {n_seen / dt:10.0f} ex/s host pipeline "
              f"({dt:.2f}s for {n_seen} examples)")


if __name__ == "__main__":
    main()
