"""Synthetic datasets, shape- and dtype-compatible with the real ones.

Used by tests and benchmarks in zero-egress environments (no CIFAR/AG
News download possible) — the data *pipeline* code paths (sharding,
prefetch, augmentation, bucketing) are identical; only the bytes are
random.  Labels are derived from the data so models can overfit them
(useful for convergence smoke tests)."""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def synthetic_cifar(n: int = 1024, seed: int = 0, num_classes: int = 10,
                    signal: float = 0.6, noise_std: float = 40.0
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(NHWC uint8 images, int32 labels) with learnable class structure:
    class k images are noise biased by a per-class mean pattern.

    The prototypes come from a FIXED rng, independent of `seed` — `seed`
    only varies labels/noise.  Different splits (train seed 0, test seed
    1) therefore share the class structure, so generalization is
    measurable; deriving prototypes from `seed` would give every split
    its own classes and pin test accuracy at chance.

    signal/noise_std tune difficulty: the defaults make an easy task
    (tests overfit it in a few steps); the accuracy-evidence convergence
    runs lower the signal so the learning curve has a real shape instead
    of saturating in epoch 1 (FDT_SYNTH_SIGNAL/FDT_SYNTH_NOISE env
    overrides, read by cli.load_dataset)."""
    rng = np.random.default_rng(seed)
    proto_rng = np.random.default_rng(20260101)
    labels = rng.integers(0, num_classes, size=n).astype(np.int32)
    prototypes = proto_rng.integers(0, 256, size=(num_classes, 32, 32, 3))
    noise = rng.normal(0, noise_std, size=(n, 32, 32, 3))
    x = np.clip(prototypes[labels] * signal + noise + 50,
                0, 255).astype(np.uint8)
    return x, labels


def synthetic_agnews(n: int = 512, seed: int = 0, vocab: int = 30522,
                     num_classes: int = 4, max_len: int = 128):
    """An AGNewsDataset-compatible object with random token sequences."""
    rng = np.random.default_rng(seed)

    class _Synthetic:
        buckets = (64, 128, 256, 512)

        def __init__(self):
            self._labels = rng.integers(0, num_classes, n).astype(np.int32)
            self._lens = rng.integers(8, max_len, n)
            # class-dependent token distribution, consistent across
            # splits: every token is congruent to the label modulo
            # num_classes (uniform noise + a shared constant would stay
            # uniform — not learnable)
            self._tokens = [
                1000 + (rng.integers(0, (vocab - 1000) // num_classes,
                                     size=ln) * num_classes
                        + self._labels[i])
                for i, ln in enumerate(self._lens)]

        def __len__(self):
            return n

        def num_classes(self):
            return num_classes

        def vocab_size(self):
            return vocab

        def encode_batch(self, indices: Sequence[int], max_len: int = 512
                         ) -> Dict[str, np.ndarray]:
            from faster_distributed_training_tpu.data.loader import (
                select_bucket)
            seqs = [self._tokens[i][:max_len - 2] for i in indices]
            longest = max(len(s) + 2 for s in seqs)
            L = select_bucket(longest, self.buckets, max_len)
            tokens = np.zeros((len(seqs), L), np.int32)
            mask = np.zeros((len(seqs), L), np.int32)
            for i, s in enumerate(seqs):
                row = [101] + list(s) + [102]
                tokens[i, :len(row)] = row
                mask[i, :len(row)] = 1
            return {"tokens": tokens,
                    "token_types": np.zeros_like(tokens),
                    "mask": mask,
                    "label": self._labels[np.asarray(indices)]}

    return _Synthetic()


def synthetic_packed_lm(n: int = 512, seed: int = 0, vocab: int = 30522,
                        seq_len: int = 128):
    """Packed rows for next-token training behind the same interface:
    ``n`` rows of exactly ``seq_len`` ids below ``vocab``, no padding (every
    mask is ones).  Each id follows its predecessor by a fixed map three
    times in four, so the loss has something to learn."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, size=(n, seq_len))
    follow = rng.random((n, seq_len)) < 0.75
    for t in range(1, seq_len):
        ids[:, t] = np.where(follow[:, t], (ids[:, t - 1] * 3 + 1) % vocab,
                             ids[:, t])
    ids = ids.astype(np.int32)

    class _Packed:
        def __len__(self):
            return n

        def num_classes(self):
            return 1

        def vocab_size(self):
            return vocab

        def encode_batch(self, indices: Sequence[int], max_len: int = 512
                         ) -> Dict[str, np.ndarray]:
            if max_len != seq_len:
                raise ValueError(f"packed rows are {seq_len} ids long, "
                                 f"asked for {max_len}")
            tokens = ids[np.asarray(indices, np.int64)]
            return {"tokens": tokens,
                    "token_types": np.zeros_like(tokens),
                    "mask": np.ones_like(tokens),
                    "label": np.zeros(len(tokens), np.int32)}

    return _Packed()
