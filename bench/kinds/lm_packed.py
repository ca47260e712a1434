"""``lm_packed``: the program's ``lm_pipeline`` — shuffled sequence ids →
documents of ``doc_len`` Zipf(``zipf_a``) token ids packed into
``seq_len + 1`` tokens → batches of ``rows``."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from bench.traffic import Reference


def packed_sequence(i: int, *, seq_len: int, vocab: int, seed: int,
                    doc_len: Tuple[int, int], zipf_a: float) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng((seed, int(i)))
    out = np.empty(seq_len + 1, np.int32)
    n = 0
    while n < out.size:
        doc = np.minimum(rng.zipf(zipf_a, int(rng.integers(*doc_len))), vocab - 1)
        take = min(doc.size, out.size - n)
        out[n : n + take] = doc[:take]
        n += take
    return {"tokens": out[:-1], "labels": out[1:]}


def pipeline(mix: Dict[str, Any], cfg: Dict[str, Any], seed: int):
    from repro.data.pipelines import lm_pipeline

    b = cfg["batch"]
    return lm_pipeline(
        cfg["vocab_size"], b["seq_len"], b["rows"],
        num_sequences=mix["num_sequences"], shuffle_buffer=mix["shuffle_buffer"], seed=seed,
    )


def reference(mix: Dict[str, Any], cfg: Dict[str, Any], seed: int) -> Reference:
    kw = dict(seq_len=cfg["batch"]["seq_len"], vocab=cfg["vocab_size"], seed=seed,
              doc_len=tuple(mix["doc_len"]), zipf_a=mix["zipf_a"])

    def make(i: int) -> Dict[str, np.ndarray]:
        return packed_sequence(i, **kw)

    def ident(i: int):
        r = make(i)
        return r["tokens"], r["labels"]

    return Reference(mix["num_sequences"], make, ident)
