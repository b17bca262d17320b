"""Seeded training batches.

A copy of the program's synthetic token stream (``repro.data.pipeline.
SyntheticTokenPipeline._make_host_batch``, decoder batches), kept here so
that the traffic cannot change under a later change to the program: batch
``step`` of seed ``seed`` is the same array in every run. Each row follows
its own affine rule over the vocabulary, so rows differ and the loss has
structure to learn.
"""
from __future__ import annotations

import numpy as np


def make_batch(seed: int, step: int, batch: int, seq: int, vocab: int) -> dict:
    """``{"tokens", "labels"}``, int32 (batch, seq); labels are the tokens
    shifted left by one (the last position wraps to the first)."""
    rng = np.random.default_rng((seed << 32) ^ step)
    a = rng.integers(1, 17, size=(batch, 1))
    c = rng.integers(0, vocab, size=(batch, 1))
    t0 = rng.integers(0, vocab, size=(batch, 1))
    idx = np.arange(seq)[None, :]
    tokens = (((a ** (idx % 5 + 1)) * t0 + c * idx) % vocab).astype(np.int32)
    return {"tokens": tokens, "labels": np.roll(tokens, -1, axis=1)}
