"""On-disk sharded dataset format for the beyond-HBM streaming tier.

The two resident layouts (data/device_resident.py) assume the train
split fits in HBM (replicated) or in the pod's aggregate HBM (sharded).
Production datasets fit in neither — they live on disk/object storage
and stream through a fixed device window (data/stream/window.py).  This
module owns the at-rest format:

  * a dataset directory holds ``shard_<i>.<leaf>.npy`` files — one raw
    ``.npy`` per leaf per shard, each covering a contiguous row range —
    plus ``manifest.json``, written LAST as the commit marker (a torn
    writer run leaves no manifest and the reader refuses the directory
    loudly instead of serving a partial split);
  * raw ``.npy`` (never ``.npz``): ``np.load(..., mmap_mode="r")`` gives
    zero-copy random row access, so the refill thread's gather is an OS
    page-cache read, not a per-shard decompress;
  * the manifest records n, per-leaf dtype/shape, the shard row table,
    per-file byte sizes (the reader cross-checks them, so a truncated
    shard file fails at open, not as silent garbage mid-epoch), and a
    per-file CRC32C (``crc32c`` + ``crc_alg``) — the size check cannot
    see a same-size byte flip, so the reader re-derives the CRC on
    first touch of each shard (riding the background window-refill
    thread) and quarantines-and-continues on mismatch
    (reader.py / resilience/sentinel.py).

Rows are addressed by GLOBAL sample index; which rows a host reads for
global batch ``b`` comes from ``loader.pod_epoch_order``'s pure
``(seed, epoch, step)`` algebra — the same function the resident layouts
gather by, which is what keeps mid-epoch resume a pure seek and the
bitwise kill-at-N pins valid across data paths (tests/test_stream.py).

``write_lm_corpus`` is the first producer: it tokenizes a text corpus
(the agnews tokenizer-resolution ladder — HF when cached, WordPiece,
hash fallback), PACKS the token stream into fixed ``[n, seq_len]`` rows
(no padding: every position is a real next-token target), and writes a
train/test doc-level split — the next-token LM workload's at-rest form.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

MANIFEST = "manifest.json"
FORMAT = "fdt-stream-v1"


def _checksum_impl():
    """(algorithm name, whole-buffer fn): CRC32C via google_crc32c when
    the wheel is present (hardware-accelerated, the GCS/TPU-fleet
    convention), else zlib's CRC32 — always available, same 32-bit
    detection strength for random bit-rot.  The manifest records which
    one signed each file (``crc_alg``), so a reader environment with a
    different library set verifies with the RIGHT polynomial or skips
    loudly instead of false-alarming."""
    try:
        import google_crc32c

        return "crc32c", lambda b: int(google_crc32c.value(bytes(b)))
    except Exception:
        import zlib

        return "crc32", lambda b: zlib.crc32(bytes(b)) & 0xFFFFFFFF


CRC_ALG, _crc_bytes = _checksum_impl()


def checksum_file(path: str, alg: str = CRC_ALG) -> Optional[int]:
    """Streaming file checksum under ``alg`` (chunked — shard files can
    exceed comfortable one-read sizes).  None when ``alg`` isn't
    computable in this environment (the reader then SKIPS verification
    for that file rather than inventing a mismatch)."""
    if alg == "crc32c":
        try:
            import google_crc32c
        except Exception:
            return None
        crc = 0
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                crc = google_crc32c.extend(crc, chunk)
        return int(crc)
    if alg == "crc32":
        import zlib
        crc = 0
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                crc = zlib.crc32(chunk, crc)
        return crc & 0xFFFFFFFF
    return None


def checksum_bytes(data) -> int:
    """One-shot checksum of an in-memory buffer under this
    environment's :data:`CRC_ALG` — the resident-upload integrity tag
    (data/device_resident.py) shares the shard files' definition."""
    return _crc_bytes(data)


def _write_npy_atomic(path: str, arr: np.ndarray) -> Tuple[int, int]:
    """np.save via tmp + os.replace so a crashed writer never leaves a
    half-written shard under its final name.  Returns (byte size,
    checksum) — the checksum re-reads what the filesystem actually
    durably holds (straight from page cache), not the array in memory,
    so a write-path corruption is signed as-is and caught at first
    verify instead of laundered into a 'valid' manifest entry."""
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        np.save(f, arr)
        f.flush()
        os.fsync(f.fileno())
    crc = checksum_file(tmp)
    os.replace(tmp, path)
    return os.path.getsize(path), int(crc or 0)


def write_stream_dataset(directory: str,
                         chunks: Iterable[Dict[str, np.ndarray]],
                         rows_per_shard: int = 4096,
                         meta: Optional[dict] = None) -> dict:
    """Write ``chunks`` (dicts of equal-leading-dim arrays) as a sharded
    stream dataset under ``directory``.  The manifest is written LAST —
    its presence is the commit marker.  Returns the manifest dict.

    Leaf dtypes/shapes must be identical across chunks (the reader mmaps
    fixed-stride rows); a mismatch raises before anything durable is
    half-written beyond shard files a re-run will overwrite."""
    rows_per_shard = int(rows_per_shard)
    if rows_per_shard < 1:
        raise ValueError(f"rows_per_shard must be >= 1, got {rows_per_shard}")
    os.makedirs(directory, exist_ok=True)
    spec: Optional[Dict[str, dict]] = None
    pending: Dict[str, List[np.ndarray]] = {}
    pending_rows = 0
    shards: List[dict] = []
    n = 0

    def flush(final: bool) -> None:
        nonlocal pending, pending_rows
        while pending_rows and (pending_rows >= rows_per_shard or final):
            take = min(pending_rows, rows_per_shard)
            idx = len(shards)
            files = {}
            rest: Dict[str, List[np.ndarray]] = {}
            for leaf, parts in pending.items():
                arr = np.concatenate(parts) if len(parts) > 1 else parts[0]
                cut, remainder = arr[:take], arr[take:]
                fname = f"shard_{idx:05d}.{leaf}.npy"
                size, crc = _write_npy_atomic(
                    os.path.join(directory, fname),
                    np.ascontiguousarray(cut))
                # end-to-end integrity: the reader re-derives this on
                # first touch of the shard (background window refill) —
                # a byte-flip keeps the size, only the CRC catches it
                files[leaf] = {"file": fname, "bytes": size,
                               "crc32c": crc, "crc_alg": CRC_ALG}
                rest[leaf] = [remainder] if len(remainder) else []
            shards.append({"rows": take, "files": files})
            pending = rest
            pending_rows -= take

    for chunk in chunks:
        if not chunk:
            continue
        got = {k: {"dtype": np.asarray(v).dtype.str,
                   "shape": list(np.asarray(v).shape[1:])}
               for k, v in chunk.items()}
        if spec is None:
            spec = got
        elif got != spec:
            raise ValueError(f"stream writer: chunk leaf spec {got} != "
                             f"first chunk's {spec} — every chunk must "
                             f"carry the same leaves/dtypes/shapes")
        rows = {len(np.asarray(v)) for v in chunk.values()}
        if len(rows) != 1:
            raise ValueError(f"stream writer: chunk leaves disagree on row "
                             f"count: { {k: len(np.asarray(v)) for k, v in chunk.items()} }")
        r = rows.pop()
        for k, v in chunk.items():
            pending.setdefault(k, []).append(np.asarray(v))
        pending_rows += r
        n += r
        flush(final=False)
    flush(final=True)
    if spec is None or n == 0:
        raise ValueError("stream writer: no rows written — empty chunk "
                         "iterable")
    manifest = {"format": FORMAT, "n": int(n), "leaves": spec,
                "shards": shards, "rows_per_shard": rows_per_shard}
    if meta:
        manifest.update(meta)
    tmp = os.path.join(directory, f"{MANIFEST}.tmp{os.getpid()}")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(directory, MANIFEST))
    return manifest


def write_array_dataset(directory: str, arrays: Dict[str, np.ndarray],
                        rows_per_shard: int = 4096,
                        meta: Optional[dict] = None) -> dict:
    """Convenience wrapper: one in-memory dict of full arrays -> shards.
    Used by scripts/shard_dataset.py and the tests; real corpora
    stream through ``write_stream_dataset``'s chunk iterable."""
    return write_stream_dataset(directory, [arrays],
                                rows_per_shard=rows_per_shard, meta=meta)


def _encode_doc(tokenizer, text: str) -> List[int]:
    """Whole-document token ids through either tokenizer interface: the
    HF/WordPiece ``encode(text, truncation=, max_length=)`` surface, or
    the HashTokenizer's positional ``encode(text, max_len)``."""
    try:
        return list(tokenizer.encode(text, truncation=True,
                                     max_length=1_000_000))
    except TypeError:
        return list(tokenizer.encode(text, 1_000_000))


def pack_lm_rows(texts: Sequence[str], tokenizer, seq_len: int,
                 chunk_docs: int = 512) -> Iterable[Dict[str, np.ndarray]]:
    """Tokenize ``texts`` doc by doc, concatenate the id streams (each
    doc keeps its CLS/SEP boundaries from the tokenizer), and cut the
    stream into PACKED ``[*, seq_len]`` int32 rows — no padding, so every
    position of every row is a real next-token target (the shifted-loss
    mask is all-ones).  The trailing partial row is dropped (static
    shapes, the drop-last training semantic).  Yields chunk dicts for
    ``write_stream_dataset``."""
    seq_len = int(seq_len)
    if seq_len < 2:
        raise ValueError(f"seq_len must be >= 2 for next-token prediction, "
                         f"got {seq_len}")
    carry: List[int] = []
    buf: List[np.ndarray] = []
    for i, text in enumerate(texts):
        carry.extend(_encode_doc(tokenizer, text))
        full = len(carry) // seq_len
        if full:
            rows = np.asarray(carry[: full * seq_len],
                              np.int32).reshape(full, seq_len)
            buf.append(rows)
            carry = carry[full * seq_len:]
        if buf and (i + 1) % chunk_docs == 0:
            yield {"tokens": np.concatenate(buf)}
            buf = []
    if buf:
        yield {"tokens": np.concatenate(buf)}


def write_lm_corpus(out_dir: str, texts: Sequence[str], seq_len: int,
                    tokenizer=None, data_dir: str = "",
                    val_fraction: float = 0.1, rows_per_shard: int = 2048,
                    seed: int = 0, clean: bool = True) -> dict:
    """Shard a text corpus for the next-token LM workload: clean (the
    agnews pipeline's cleaner, so a cached WordPiece vocab matches),
    resolve a tokenizer (HF -> WordPiece -> hash, data/agnews.py ladder),
    split DOCUMENTS train/test (deterministic in ``seed`` — packing
    after the split keeps held-out text genuinely unseen), pack each
    split into ``[n, seq_len]`` rows and write ``<out_dir>/train`` +
    ``<out_dir>/test``.  Returns {"train": manifest, "test": manifest,
    "vocab_size": V}."""
    from faster_distributed_training_tpu.data.agnews import (
        _resolve_tokenizer, clean_text)

    docs = [clean_text(t) if clean else str(t) for t in texts]
    docs = [d for d in docs if d.strip()]
    if len(docs) < 2:
        raise ValueError(f"LM corpus needs >= 2 non-empty documents, got "
                         f"{len(docs)}")
    if tokenizer is None:
        tokenizer = _resolve_tokenizer(data_dir, docs)
    order = np.random.default_rng(seed).permutation(len(docs))
    n_test = max(1, int(round(len(docs) * float(val_fraction))))
    test_docs = [docs[i] for i in order[:n_test]]
    train_docs = [docs[i] for i in order[n_test:]]
    vocab = int(getattr(tokenizer, "vocab_size", 30522))
    # "content" (not "kind"): the telemetry schema lint reserves literal
    # "kind" dict keys for JSONL event dicts (scripts/
    # check_telemetry_schema.py scans every dict literal in the package)
    meta = {"content": "lm", "seq_len": int(seq_len), "vocab_size": vocab,
            "tokenizer": type(tokenizer).__name__}
    out = {"vocab_size": vocab}
    for split, split_docs in (("train", train_docs), ("test", test_docs)):
        out[split] = write_stream_dataset(
            os.path.join(out_dir, split),
            pack_lm_rows(split_docs, tokenizer, seq_len),
            rows_per_shard=rows_per_shard,
            meta={**meta, "split": split, "docs": len(split_docs)})
    return out


def synthetic_corpus(n_docs: int = 256, seed: int = 0,
                     words_per_doc: Tuple[int, int] = (30, 120),
                     vocab_words: int = 600) -> List[str]:
    """Deterministic pseudo-text corpus for zero-egress environments:
    word-like strings drawn zipf-ish from a fixed fake vocabulary, so
    the WordPiece/hash tokenizers produce a learnable (skewed, repeated)
    token distribution rather than uniform noise."""
    rng = np.random.default_rng(seed)
    syll = ["ka", "ro", "mi", "ten", "lu", "za", "por", "eni", "sta", "vel",
            "dor", "ashi", "qu", "ber", "on", "tra", "ix", "mel", "gra", "un"]
    words = ["".join(syll[j % len(syll)]
                     for j in rng.integers(0, len(syll), size=ln))
             for ln in rng.integers(2, 5, size=vocab_words)]
    ranks = np.arange(1, vocab_words + 1, dtype=np.float64)
    p = (1.0 / ranks) / np.sum(1.0 / ranks)
    docs = []
    for _ in range(int(n_docs)):
        k = int(rng.integers(*words_per_doc))
        docs.append(" ".join(words[i]
                             for i in rng.choice(vocab_words, size=k, p=p)))
    return docs
