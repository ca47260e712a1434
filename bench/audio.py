"""Whisper's audio front end on the host, in NumPy.

``log_mel`` computes the log-mel spectrogram as Whisper-large-v3 does: a
periodic Hann window of ``n_fft`` samples, hop ``hop``, centred frames with
reflect padding, the last frame dropped, power spectrum, a Slaney-style mel
filterbank (``librosa.filters.mel`` defaults: Slaney mel scale, area
normalisation, 0 Hz to Nyquist), ``log10`` clamped at 1e-10, floored at
8 below the clip's maximum, then ``(x + 4) / 4``.

The repository's Whisper model takes ``enc_embeds`` in place of its
convolutional front end, which it does not implement.  ``enc_embeds`` stands
in for it: each pair of consecutive frames (stride 2, as the second conv
has) is concatenated and multiplied by a fixed seeded projection to
``d_model``.
"""
from __future__ import annotations

import functools

import numpy as np


def _hz_to_mel(f: np.ndarray) -> np.ndarray:
    f = np.asarray(f, np.float64)
    f_sp, min_log_hz = 200.0 / 3, 1000.0
    min_log_mel, logstep = min_log_hz / f_sp, np.log(6.4) / 27.0
    lin = f / f_sp
    return np.where(f >= min_log_hz, min_log_mel + np.log(np.maximum(f, 1e-10) / min_log_hz) / logstep, lin)


def _mel_to_hz(m: np.ndarray) -> np.ndarray:
    m = np.asarray(m, np.float64)
    f_sp, min_log_hz = 200.0 / 3, 1000.0
    min_log_mel, logstep = min_log_hz / f_sp, np.log(6.4) / 27.0
    return np.where(m >= min_log_mel, min_log_hz * np.exp(logstep * (m - min_log_mel)), f_sp * m)


@functools.lru_cache(maxsize=4)
def mel_filters(sample_rate: int, n_fft: int, n_mels: int) -> np.ndarray:
    """(n_mels, n_fft // 2 + 1) Slaney filterbank, float32."""
    fft_freqs = np.linspace(0, sample_rate / 2, n_fft // 2 + 1)
    mel_pts = _mel_to_hz(np.linspace(_hz_to_mel(0.0), _hz_to_mel(sample_rate / 2), n_mels + 2))
    fdiff = np.diff(mel_pts)
    ramps = mel_pts[:, None] - fft_freqs[None, :]
    lower = -ramps[:-2] / fdiff[:-1, None]
    upper = ramps[2:] / fdiff[1:, None]
    weights = np.maximum(0, np.minimum(lower, upper))
    weights *= (2.0 / (mel_pts[2 : n_mels + 2] - mel_pts[:n_mels]))[:, None]
    return weights.astype(np.float32)


@functools.lru_cache(maxsize=4)
def hann(n: int) -> np.ndarray:
    return (0.5 - 0.5 * np.cos(2 * np.pi * np.arange(n) / n)).astype(np.float32)


def log_mel(audio: np.ndarray, sample_rate: int, n_fft: int, hop: int, n_mels: int) -> np.ndarray:
    """(n_mels, len(audio) // hop) log-mel spectrogram, float32."""
    pad = n_fft // 2
    x = np.pad(audio.astype(np.float32), pad, mode="reflect")
    n_frames = 1 + (x.size - n_fft) // hop
    frames = np.lib.stride_tricks.as_strided(
        x, shape=(n_frames, n_fft), strides=(x.strides[0] * hop, x.strides[0]), writeable=False
    )
    spec = np.fft.rfft(frames * hann(n_fft), axis=-1)[:-1]
    power = (spec.real**2 + spec.imag**2).astype(np.float32)
    mel = mel_filters(sample_rate, n_fft, n_mels) @ power.T
    logs = np.log10(np.maximum(mel, 1e-10))
    logs = np.maximum(logs, logs.max() - 8.0)
    return ((logs + 4.0) / 4.0).astype(np.float32)


def synth_audio(rng: np.random.Generator, n: int, sample_rate: int) -> np.ndarray:
    """Seeded speech-like audio: a voiced tone whose pitch wanders, with
    harmonics, syllable-rate amplitude and background noise, float32."""
    t = np.arange(n, dtype=np.float64) / sample_rate
    f0 = rng.uniform(90, 250) * (1 + 0.1 * np.sin(2 * np.pi * rng.uniform(0.2, 1.0) * t))
    phase = 2 * np.pi * np.cumsum(f0) / sample_rate
    voiced = sum(np.sin(k * phase) / k for k in range(1, 6))
    envelope = np.clip(np.sin(2 * np.pi * rng.uniform(2, 5) * t + rng.uniform(0, 6.3)), 0, None)
    noise = rng.standard_normal(n)
    return (0.3 * voiced * envelope + 0.02 * noise).astype(np.float32)


@functools.lru_cache(maxsize=4)
def frame_projection(seed: int, rows: int, cols: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((rows, cols)) / np.sqrt(rows)).astype(np.float32)


def enc_embeds(mel: np.ndarray, projection: np.ndarray) -> np.ndarray:
    """Stride-2 frame pairs of a (n_mels, frames) spectrogram, projected:
    (frames // 2, d_model), float32."""
    n_mels, frames = mel.shape
    pairs = np.ascontiguousarray(mel.T).reshape(frames // 2, 2 * n_mels)
    return pairs @ projection
