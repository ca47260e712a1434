"""Canonical input pipelines mirroring the paper's workload domains.

The paper evaluates vision models (M1-M4, ResNet50/ImageNet+AutoAugment) and
NLP models (M5-M8, variable sequence length).  We provide equivalent
open pipelines with *registered* (serializable) UDFs:

* ``vision_pipeline`` — decode (simulated JPEG-cost) → random crop → flip →
  AutoAugment-like photometric ops → normalize → batch.  Heavy per-element
  CPU cost ⇒ input-bound jobs; the horizontal scale-out benchmark uses it.
* ``nlp_pipeline``   — tokenized variable-length sequences → (optional)
  bucket-by-length → padded batch.  Feeds the coordinated-reads benchmark.
* ``lm_pipeline``    — synthetic Zipf documents packed into fixed-length
  next-token sequences → batch.  Feeds the LM training examples and
  ``chip_smoke.py``.

Work knobs are explicit (``work_factor``) so benchmarks can dial
preprocessing cost to reproduce both input-bound and model-bound regimes.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .dataset import Dataset
from .graph import AUTOTUNE
from .registry import register


# ---------------------------------------------------------------------------
# Synthetic sources
# ---------------------------------------------------------------------------
@register("synthetic_raw_image")
def synthetic_raw_image(i: Any, *, size: int = 64, seed: int = 0) -> Dict[str, Any]:
    """Deterministic pseudo-'encoded' image: byte payload + label."""
    rng = np.random.RandomState((int(i) + seed * 1_000_003) & 0x7FFFFFFF)
    raw = rng.randint(0, 256, size=(size, size, 3), dtype=np.uint8)
    return {"raw": raw, "label": np.int64(int(i) % 1000), "index": np.int64(int(i))}


@register("synthetic_token_seq")
def synthetic_token_seq(
    i: Any, *, max_len: int = 512, vocab: int = 32000, seed: int = 0
) -> Dict[str, Any]:
    """Variable-length token sequence with a long-tail length distribution
    (mimics NLP corpora; drives straggler effects in distributed training)."""
    rng = np.random.RandomState((int(i) * 2_654_435 + seed) & 0x7FFFFFFF)
    # lognormal length, clipped to [4, max_len]
    ln = int(np.clip(rng.lognormal(mean=4.0, sigma=0.8), 4, max_len))
    toks = rng.randint(1, vocab, size=(ln,), dtype=np.int32)
    return {"tokens": toks, "length": np.int64(ln), "index": np.int64(int(i))}


@register("packed_lm_sequence")
def packed_lm_sequence(
    i: Any, *, seq_len: int, vocab: int, seed: int = 0
) -> Dict[str, np.ndarray]:
    """Packed sequence ``i``: documents of 64–511 Zipf-distributed token
    ids (1 ≤ id < vocab, a real tokenizer's skew) drawn from a generator
    seeded by ``(seed, i)`` and concatenated until ``seq_len + 1`` tokens,
    then split into next-token ``tokens`` and ``labels``.  A pure function
    of ``i``, so any execution order yields the same sequence for ``i``."""
    rng = np.random.default_rng((seed, int(i)))
    out = np.empty(seq_len + 1, np.int32)
    n = 0
    while n < out.size:
        doc = np.minimum(rng.zipf(1.3, int(rng.integers(64, 512))), vocab - 1)
        take = min(doc.size, out.size - n)
        out[n : n + take] = doc[:take]
        n += take
    return {"tokens": out[:-1], "labels": out[1:]}


# ---------------------------------------------------------------------------
# Vision transforms (decode + augment; the input-bound hot path)
# ---------------------------------------------------------------------------
@register("simulate_decode")
def simulate_decode(elem: Dict[str, Any], *, work_factor: int = 1) -> Dict[str, Any]:
    """Simulated JPEG decode: real FLOPs proportional to image size.

    Uses a DCT-like transform so the CPU cost profile matches decode+IDCT
    (the dominant cost in the paper's vision pipelines).
    """
    img = elem["raw"].astype(np.float32) / 255.0
    for _ in range(max(1, work_factor)):
        # 2D transform along W per channel — O(H*W*K) like a real IDCT
        img = np.tanh(np.einsum("hwc,wk->hkc", img, _dct_matrix(img.shape[1])))
    return {"image": img, "label": elem["label"], "index": elem["index"]}


_DCT_CACHE: Dict[int, np.ndarray] = {}


def _dct_matrix(n: int) -> np.ndarray:
    if n not in _DCT_CACHE:
        k = np.arange(n)
        _DCT_CACHE[n] = np.cos(np.pi / n * np.outer(k + 0.5, k)).astype(np.float32) / n
    return _DCT_CACHE[n]


@register("random_crop_flip")
def random_crop_flip(
    elem: Dict[str, Any], *, crop: int = 56, seed: int = 0
) -> Dict[str, Any]:
    img = elem["image"]
    rng = np.random.RandomState((int(elem["index"]) + seed) & 0x7FFFFFFF)
    h, w = img.shape[:2]
    if h > crop and w > crop:
        y, x = rng.randint(0, h - crop), rng.randint(0, w - crop)
        img = img[y : y + crop, x : x + crop]
    if rng.rand() < 0.5:
        img = img[:, ::-1]
    return {"image": np.ascontiguousarray(img), "label": elem["label"], "index": elem["index"]}


@register("autoaugment_like")
def autoaugment_like(elem: Dict[str, Any], *, seed: int = 0, ops: int = 2) -> Dict[str, Any]:
    """AutoAugment-style photometric policy (contrast/brightness/posterize/
    sharpen-ish convolutions) — the expensive augmentation in the paper's
    ResNet50 experiment."""
    img = elem["image"]
    rng = np.random.RandomState((int(elem["index"]) * 97 + seed) & 0x7FFFFFFF)
    for _ in range(ops):
        choice = rng.randint(0, 4)
        if choice == 0:  # contrast
            img = np.clip((img - img.mean()) * (0.5 + rng.rand()) + img.mean(), 0, 1)
        elif choice == 1:  # brightness
            img = np.clip(img + (rng.rand() - 0.5) * 0.4, 0, 1)
        elif choice == 2:  # posterize
            bits = rng.randint(4, 8)
            img = np.floor(img * (2**bits)) / (2**bits)
        else:  # 3x3 blur (separable)
            kernel = np.array([0.25, 0.5, 0.25], dtype=np.float32)
            img = _sep_conv3(img, kernel)
    return {"image": img.astype(np.float32), "label": elem["label"], "index": elem["index"]}


def _sep_conv3(img: np.ndarray, k: np.ndarray) -> np.ndarray:
    pad = np.pad(img, ((1, 1), (0, 0), (0, 0)), mode="edge")
    img = k[0] * pad[:-2] + k[1] * pad[1:-1] + k[2] * pad[2:]
    pad = np.pad(img, ((0, 0), (1, 1), (0, 0)), mode="edge")
    return k[0] * pad[:, :-2] + k[1] * pad[:, 1:-1] + k[2] * pad[:, 2:]


@register("normalize_image")
def normalize_image(elem: Dict[str, Any]) -> Dict[str, Any]:
    img = (elem["image"] - 0.45) / 0.225
    return {"image": img.astype(np.float32), "label": elem["label"]}


# ---------------------------------------------------------------------------
# NLP helpers
# ---------------------------------------------------------------------------
@register("seq_length")
def seq_length(elem: Dict[str, Any]) -> int:
    return int(elem["length"])


@register("batch_bucket_key")
def batch_bucket_key(batch: Dict[str, Any]) -> int:
    return int(batch["_bucket"])


@register("identity_window")
def identity_window(window: List[Any]) -> List[Any]:
    return window


# ---------------------------------------------------------------------------
# Pipeline factories
# ---------------------------------------------------------------------------
def vision_pipeline(
    num_elements: int = 1024,
    batch_size: int = 32,
    image_size: int = 64,
    crop: int = 56,
    work_factor: int = 1,
    parallelism: int = AUTOTUNE,
    shuffle_buffer: int = 256,
    seed: int = 0,
) -> Dataset:
    ds = Dataset.range(num_elements)
    ds = ds.map(synthetic_raw_image, size=image_size, seed=seed)
    ds = ds.shuffle(shuffle_buffer, seed=seed)
    ds = ds.map(
        simulate_decode, num_parallel_calls=parallelism, work_factor=work_factor
    )
    ds = ds.map(random_crop_flip, stochastic=True, crop=crop, seed=seed)
    ds = ds.map(autoaugment_like, stochastic=True, seed=seed)
    ds = ds.map(normalize_image)
    ds = ds.batch(batch_size, drop_remainder=True)
    return ds


def nlp_pipeline(
    num_elements: int = 4096,
    batch_size: int = 16,
    max_len: int = 512,
    vocab: int = 32000,
    bucket_boundaries: Optional[Sequence[int]] = None,
    num_consumers: int = 0,
    seed: int = 0,
) -> Dataset:
    """Variable-length NLP pipeline.

    Without buckets: naive padded-batch to the max length in each batch.
    With buckets (+ optional num_consumers): the paper's coordinated-reads
    front-end (Fig. 7) — bucket_by_sequence_length → group_by_window(m) →
    flat_map.
    """
    ds = Dataset.range(num_elements)
    ds = ds.map(synthetic_token_seq, max_len=max_len, vocab=vocab, seed=seed)
    if bucket_boundaries is None:
        return ds.padded_batch(batch_size, drop_remainder=True)
    ds = ds.bucket_by_sequence_length(
        boundaries=list(bucket_boundaries),
        batch_size=batch_size,
        length_fn=seq_length,
        drop_remainder=True,
        emit_bucket_id=True,
        pad_to_boundary=True,
    )
    if num_consumers > 1:
        ds = ds.group_by_window(
            key_fn=batch_bucket_key, window_size=num_consumers, drop_remainder=True
        )
        ds = ds.flat_map(identity_window)
    return ds


def lm_pipeline(
    vocab: int,
    seq_len: int,
    batch_size: int,
    num_sequences: int = 100_000,
    shuffle_buffer: int = 2048,
    seed: int = 0,
) -> Dataset:
    """LM pretraining input, run on the workers: shuffled sequence ids →
    packed Zipf documents (``packed_lm_sequence``) → batches of
    ``batch_size`` × ``seq_len`` int32 tokens and labels."""
    return (
        Dataset.range(num_sequences)
        .shuffle(shuffle_buffer, seed=seed)
        .map(packed_lm_sequence, seq_len=seq_len, vocab=vocab, seed=seed)
        .batch(batch_size, drop_remainder=True)
        .prefetch(8)
    )


def materialized(ds: Dataset, snapshot_path: str, tail: bool = False) -> Dataset:
    """Swap a pipeline for its materialized snapshot when one is available.

    The manual (policy-free) entry point to snapshot reuse: if a finished
    snapshot exists at ``snapshot_path`` — or any snapshot exists and
    ``tail=True`` — return a dataset reading it (zero recomputation);
    otherwise return ``ds`` unchanged so the caller computes as usual.
    Pair with ``repro.core.materialize`` to write the snapshot; use
    ``autocache=True`` on ``Dataset.distribute`` for the cost-model-driven
    version of this decision.
    """
    from ..snapshot.reader import snapshot_exists, snapshot_finished

    if snapshot_finished(snapshot_path) or (tail and snapshot_exists(snapshot_path)):
        return Dataset.from_snapshot(snapshot_path, tail=tail)
    return ds
