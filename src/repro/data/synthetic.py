"""Synthetic dataset generators matching the paper's experimental data.

  * ``synth_imagenet_features`` — the paper's §IV-A weak/strong-scaling data:
    dense feature vectors (they used 160K-dim featurized ImageNet) with
    labels from a random ground-truth separator + noise, so logistic
    regression has a recoverable optimum.
  * ``synth_netflix_tiled`` — the paper's §IV-B collaborative-filtering data:
    a base low-rank + noise ratings matrix with Netflix-like sparsity,
    *tiled* t× to scale exactly the way the paper scales ("repeatedly tiling
    the Netflix dataset ... maintains the sparsity structure").
  * ``synth_classification`` — small dense classification sets for tests.
  * ``synth_text_corpus`` / ``SyntheticLMDataset`` — text for the Fig. A2
    pipeline and token streams for transformer training.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

__all__ = ["synth_classification", "synth_imagenet_features",
           "synth_netflix_tiled", "synth_text_corpus", "synth_labeled_text",
           "SyntheticLMDataset"]


def synth_classification(n: int, d: int, seed: int = 0, noise: float = 0.05
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Linearly separable-ish binary data.  Returns (X, y, w_true)."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=d) / np.sqrt(d)
    X = rng.normal(size=(n, d)).astype(np.float32)
    margin = X @ w
    flip = rng.random(n) < noise
    y = ((margin > 0) ^ flip).astype(np.float32)
    return X, y, w.astype(np.float32)


def synth_imagenet_features(n: int, d: int = 4096, seed: int = 0,
                            dtype=np.float64
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Dense featurized-image stand-in (paper used d=160K; tests scale d
    down).  Features are ReLU'd gaussians (non-negative, sparse-ish like
    conv features); labels from a planted linear model.

    ``dtype`` is the precision the gaussians are drawn in; the returned
    ``X`` is float32 either way.  ``np.float32`` draws other values from
    the same seed but never builds an (n, d) float64 intermediate, which
    at the paper's width is twice the table."""
    rng = np.random.default_rng(seed)
    if np.dtype(dtype) == np.float32:
        X = rng.standard_normal(size=(n, d), dtype=np.float32)
        np.maximum(X, 0, out=X)
        w = rng.standard_normal(size=d, dtype=np.float32) / np.float32(np.sqrt(d))
    else:
        X = np.maximum(rng.normal(size=(n, d)), 0).astype(np.float32)
        w = rng.normal(size=d) / np.sqrt(d)
    margin = X @ w
    y = (margin > np.median(margin)).astype(np.float32)
    return X, y


def synth_netflix_tiled(
    users: int = 480, items: int = 178, rank: int = 10, tiles: int = 1,
    density: float = 0.011, seed: int = 0,
) -> np.ndarray:
    """Dense (users·t, items·t) ratings matrix with zeros for unobserved
    entries (the paper's CSR partitions become fixed-shape dense blocks with
    an explicit zero = unobserved convention; see LocalMatrix notes).

    Default users/items keep the Netflix user:item ratio (480K:17.8K) at
    1/1000 scale; ``tiles`` scales the matrix exactly as the paper does —
    block-diagonal tiling preserves per-row/column sparsity structure."""
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(users, rank)) / np.sqrt(rank)
    V = rng.normal(size=(items, rank)) / np.sqrt(rank)
    base = U @ V.T + 0.1 * rng.normal(size=(users, items))
    base = np.clip(2.5 + 1.5 * base, 1.0, 5.0)
    mask = rng.random((users, items)) < density
    base = np.where(mask, base, 0.0).astype(np.float32)
    if tiles == 1:
        return base
    out = np.zeros((users * tiles, items * tiles), np.float32)
    for t in range(tiles):
        out[t * users:(t + 1) * users, t * items:(t + 1) * items] = base
    return out


_WORDS = ("the quick brown fox jumps over lazy dog machine learning api "
          "distributed table matrix gradient descent cluster spark data "
          "feature model train test scale pod mesh kernel").split()


def synth_text_corpus(n_docs: int = 64, words_per_doc: int = 30,
                      seed: int = 0) -> list:
    """Tiny synthetic corpus for the Fig. A2 pipeline (nGrams → tfIdf →
    KMeans).  Docs are drawn from topic-biased word distributions so
    clustering has structure to find."""
    rng = np.random.default_rng(seed)
    n_topics = 4
    topic_bias = rng.dirichlet(np.ones(len(_WORDS)) * 0.3, size=n_topics)
    docs = []
    for i in range(n_docs):
        p = topic_bias[i % n_topics]
        docs.append(" ".join(rng.choice(_WORDS, size=words_per_doc, p=p)))
    return docs


def synth_labeled_text(n_docs: int = 64, words_per_doc: int = 20,
                       seed: int = 0) -> list:
    """Binary text-classification corpus for the Fig. A2 end-to-end story:
    ``(label, text)`` rows whose word distributions are class-biased (each
    class favors half the vocabulary), so a served text pipeline has
    signal to learn.  Pure function of the arguments — a resumed run sees
    the identical table."""
    rng = np.random.default_rng(seed)
    half = len(_WORDS) // 2
    bias = np.full(len(_WORDS), 0.25 / (len(_WORDS) - half))
    p0, p1 = bias.copy(), bias.copy()
    p0[:half] = 0.75 / half
    p1[half:] = 0.75 / (len(_WORDS) - half)
    p0, p1 = p0 / p0.sum(), p1 / p1.sum()
    rows = []
    for i in range(n_docs):
        label = i % 2
        p = p1 if label else p0
        rows.append((float(label),
                     " ".join(rng.choice(_WORDS, size=words_per_doc, p=p))))
    return rows


@dataclasses.dataclass
class SyntheticLMDataset:
    """Deterministic token stream for transformer train/serve examples.

    Tokens follow a planted bigram chain (so a trained model has signal to
    learn: next-token ≈ (token * mult + inc) % vocab with noise)."""
    vocab_size: int
    seq_len: int
    batch_size: int
    seed: int = 0
    noise: float = 0.1

    def batch(self, step: int) -> dict:
        rng = np.random.default_rng(self.seed + step)
        B, S = self.batch_size, self.seq_len
        mult = 31
        toks = np.zeros((B, S), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab_size, size=B)
        for t in range(1, S):
            nxt = (toks[:, t - 1] * mult + 7) % self.vocab_size
            noise_mask = rng.random(B) < self.noise
            rand = rng.integers(0, self.vocab_size, size=B)
            toks[:, t] = np.where(noise_mask, rand, nxt)
        return {"tokens": toks, "labels": toks.copy()}
