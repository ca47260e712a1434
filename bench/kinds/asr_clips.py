"""``asr_clips``: seeded clips of speech-like audio → Whisper log-mel
spectrogram → ``enc_embeds`` (``bench.audio``) and a transcript of lognormal
length, Zipf token ids, padded with label 0.  The map runs on the workers."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from bench import audio
from bench.traffic import PAD_ID, Reference


def transcript(i: int, *, seed: int, length: Dict[str, float], seq_len: int, vocab: int,
               zipf_a: float) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng((seed, int(i), 1))
    n = int(np.clip(rng.lognormal(length["mean"], length["sigma"]), length["min"], length["max"]))
    ids = np.minimum(rng.zipf(zipf_a, n + 1), vocab - 1).astype(np.int32)
    tokens = np.full(seq_len, PAD_ID, np.int32)
    labels = np.full(seq_len, PAD_ID, np.int32)
    tokens[:n], labels[:n] = ids[:-1], ids[1:]
    return tokens, labels


def asr_clip(i: Any, *, seed: int, sample_rate: int, clip_seconds: int, n_fft: int, hop: int,
             n_mels: int, d_model: int, projection_seed: int, length: Dict[str, float],
             seq_len: int, vocab: int, zipf_a: float) -> Dict[str, np.ndarray]:
    """Clip ``i``: its ``enc_embeds``, decoder ``tokens`` and ``labels``."""
    rng = np.random.default_rng((seed, int(i), 0))
    wave = audio.synth_audio(rng, sample_rate * clip_seconds, sample_rate)
    mel = audio.log_mel(wave, sample_rate, n_fft, hop, n_mels)
    proj = audio.frame_projection(projection_seed, 2 * n_mels, d_model)
    tokens, labels = transcript(i, seed=seed, length=length, seq_len=seq_len, vocab=vocab,
                                zipf_a=zipf_a)
    return {"enc_embeds": audio.enc_embeds(mel, proj), "tokens": tokens, "labels": labels}


def _kwargs(mix: Dict[str, Any], cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    return dict(seed=seed, sample_rate=mix["sample_rate"], clip_seconds=mix["clip_seconds"],
                n_fft=mix["n_fft"], hop=mix["hop"], n_mels=mix["n_mels"], d_model=cfg["d_model"],
                projection_seed=mix["projection_seed"], length=mix["transcript_length"],
                seq_len=cfg["batch"]["seq_len"], vocab=cfg["vocab_size"], zipf_a=mix["zipf_a"])


def pipeline(mix: Dict[str, Any], cfg: Dict[str, Any], seed: int):
    from repro.data import Dataset

    return (
        Dataset.range(mix["num_clips"])
        .shuffle(mix["shuffle_buffer"], seed=seed)
        .map(asr_clip, **_kwargs(mix, cfg, seed))
        .batch(cfg["batch"]["rows"], drop_remainder=True)
        .prefetch(mix["prefetch"])
    )


def reference(mix: Dict[str, Any], cfg: Dict[str, Any], seed: int) -> Reference:
    kw = _kwargs(mix, cfg, seed)
    tkw = {k: kw[k] for k in ("seed", "length", "seq_len", "vocab", "zipf_a")}
    return Reference(mix["num_clips"], lambda i: asr_clip(i, **kw),
                     lambda i: transcript(i, **tkw))
