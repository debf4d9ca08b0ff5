"""The one traffic generator: a data set made from ``--seed`` and the
parameters of a traffic file (``benchmark/traffic/<traffic>.json``, key
``data``).  The program receives only what comes out of here, through its
own loaders.  Two kinds, both copies of the program's
``data/synthetic.py`` arithmetic (listed in PERF.md for a later PR to
retire the original): ``images`` (uint8 NHWC rows with a per-class
pattern) and ``texts`` (token rows of uniform length, every token
congruent to the label).  A new mix is a new traffic file, not new code.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

# fixed, so that every seed shares the class structure (as the program's
# synthetic data does): only labels, noise and lengths follow the seed
_PROTOTYPE_SEED = 20260101
_M64 = (1 << 64) - 1


def seed32(seed: int) -> int:
    """``--seed`` may be a little over 2**31 and a PRNGKey wants it inside
    int32: splitmix64's finaliser, then modulo 2**31 - 1.  A hash and not a
    plain modulo, which gave seeds 101 and 2**31 + 99 the same data and
    weights."""
    z = (int(seed) + 0x9E3779B97F4A7C15) & _M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return int((z ^ (z >> 31)) % (2 ** 31 - 1))


def images(p: dict, seed: int):
    """(uint8 [n, h, w, c], int32 [n]) — class k is noise round a fixed
    per-class pattern.  Made in bulk on the default device in one jitted
    call and read back (numpy's normal draws took 7 s for 50,000 rows on
    the chip's host, my chip run, PR 23)."""
    import jax
    import jax.numpy as jnp
    n, classes = int(p["rows"]), int(p["classes"])
    h, w, c = (int(v) for v in p["shape"])
    proto = np.random.default_rng(_PROTOTYPE_SEED).integers(
        0, 256, size=(classes, h, w, c)).astype(np.float32)
    signal, noise_std = float(p["signal"]), float(p["noise_std"])

    @jax.jit
    def make(key, proto):
        k_lab, k_noise = jax.random.split(key)
        labels = jax.random.randint(k_lab, (n,), 0, classes, jnp.int32)
        noise = jax.random.normal(k_noise, (n, h, w, c), jnp.float32)
        x = proto[labels] * signal + noise * noise_std + 50.0
        return jnp.clip(x, 0, 255).astype(jnp.uint8), labels

    x, labels = make(jax.random.PRNGKey(seed32(seed)), proto)
    return np.asarray(x), np.asarray(labels)


class Texts:
    """What the program's text loaders need of a data set:
    ``encode_batch`` pads every batch to the smallest bucket that holds
    its longest row, [CLS] ... [SEP] round the tokens."""

    def __init__(self, p: dict, seed: int):
        n, self._classes = int(p["rows"]), int(p["classes"])
        self._vocab = int(p["vocab"])
        self.buckets = tuple(int(b) for b in p["buckets"])
        lo, hi = (int(v) for v in p["length"])       # tokens, hi exclusive
        rng = np.random.default_rng(seed32(seed))
        self._labels = rng.integers(0, self._classes, n).astype(np.int32)
        self._lens = rng.integers(lo, hi, n)
        self._offsets = np.concatenate([[0], np.cumsum(self._lens)])
        body = rng.integers(0, (self._vocab - 1000) // self._classes,
                            size=int(self._offsets[-1]))
        self._tokens = (1000 + body * self._classes
                        + np.repeat(self._labels, self._lens)
                        ).astype(np.int32)

    def __len__(self) -> int:
        return len(self._labels)

    def num_classes(self) -> int:
        return self._classes

    def vocab_size(self) -> int:
        return self._vocab

    def row(self, i: int, max_len: int) -> np.ndarray:
        s = self._tokens[self._offsets[i]:self._offsets[i + 1]][:max_len - 2]
        return np.concatenate([[101], s, [102]]).astype(np.int32)

    def label(self, i: int) -> int:
        return int(self._labels[i])

    def encode_batch(self, indices: Sequence[int], max_len: int = 512
                     ) -> Dict[str, np.ndarray]:
        rows = [self.row(int(i), max_len) for i in indices]
        longest = max(len(r) for r in rows)
        fits = [b for b in self.buckets if longest <= b <= max_len]
        width = min(fits) if fits else max_len
        tokens = np.zeros((len(rows), width), np.int32)
        mask = np.zeros((len(rows), width), np.int32)
        for i, r in enumerate(rows):
            tokens[i, :len(r)] = r
            mask[i, :len(r)] = 1
        return {"tokens": tokens, "token_types": np.zeros_like(tokens),
                "mask": mask, "label": self._labels[np.asarray(indices)]}


KINDS = {"images": images, "texts": Texts}


def generate(p: dict, seed: int):
    if p["kind"] not in KINDS:
        raise ValueError(f"traffic kind {p['kind']!r}; have {sorted(KINDS)}")
    return KINDS[p["kind"]](p, seed)
