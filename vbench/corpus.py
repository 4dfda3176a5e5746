"""Review corpora generated from a seed: the benchmark's own yardstick copy.

The arithmetic follows the repository's synthetic review generator (planted
rating-dependent topics, per-user rating biases, helpfulness votes tied to
writing quality, a share of off-topic reviews), vectorised so that a corpus
of millions of tokens takes seconds of set-up. It is kept here so that
later changes to the program cannot move the benchmark's inputs.

Sizes (groups, documents per group, tokens per document) are drawn once from
the configuration's fixed ``shape_seed``: every run seed sees the same shapes,
so every compiled program is the same, and the run seed only changes the
content and the order of the document lengths inside each group.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Group:
    """One corpus (a product's reviews, or one document collection)."""

    tokens: np.ndarray  # (N,) int32 base-vocabulary word ids, doc-major
    doc_len: np.ndarray  # (D,) int64 tokens per document
    rating: np.ndarray  # (D,) float64 stars 1..5
    user: np.ndarray  # (D,) int64
    helpful: np.ndarray  # (D,) int64
    unhelpful: np.ndarray  # (D,) int64
    writing_quality: np.ndarray  # (D,) float64

    @property
    def num_docs(self) -> int:
        return int(self.doc_len.shape[0])

    @property
    def num_tokens(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def doc_of_token(self) -> np.ndarray:
        return np.repeat(np.arange(self.num_docs), self.doc_len)


def _power_classes(n: int, exponent: float, lo: float, hi: float,
                   classes: int) -> np.ndarray:
    """`n` group sizes from p(x) ∝ x^-exponent on [lo, hi], in `classes`
    log-spaced size classes: each class takes its share of the law's mass
    (at least one group) at the law's mean size within the class."""
    classes = min(classes, n)
    x = np.exp(np.linspace(np.log(lo), np.log(hi), 200_001))
    p = x ** (1.0 - exponent)  # density per unit of log x
    cls = np.minimum((np.log(x / lo) / np.log(hi / lo) * classes).astype(
        np.int64), classes - 1)
    mass = np.bincount(cls, p)
    mean = np.bincount(cls, p * x) / mass
    mass /= mass.sum()
    count = np.maximum(1, np.floor(mass * n)).astype(np.int64)
    while count.sum() < n:
        count[np.argmax(mass * n - count)] += 1
    while count.sum() > n:
        count[np.argmax(np.where(count > 1, count - mass * n, -np.inf))] -= 1
    return np.repeat(np.round(mean).astype(np.int64), count)


def group_sizes(spec: dict) -> np.ndarray:
    """Documents per group, fixed by the configuration alone."""
    law = spec["docs_per_group"]
    if law["law"] == "fixed":
        return np.full(spec["groups"], int(law["value"]), np.int64)
    if law["law"] == "power":
        sizes = _power_classes(spec["groups"], float(law["exponent"]),
                               float(law["min"]), float(law["max"]),
                               int(law["classes"]))
        # Interleave large and small groups so that no order of the
        # catalog puts all the large ones first.
        return sizes[np.random.default_rng(spec["shape_seed"]).permutation(
            len(sizes))]
    raise ValueError(f"unknown docs_per_group law {law['law']!r}")


def doc_lengths(spec: dict) -> list[np.ndarray]:
    """Tokens per document for every group, fixed by the configuration;
    groups of one size share one set of lengths."""
    rng = np.random.default_rng(spec["shape_seed"] + 1)
    law = spec["tokens_per_doc"]
    out, by_size = [], {}
    for d in group_sizes(spec):
        if int(d) in by_size:
            out.append(by_size[int(d)])
            continue
        if law["law"] == "poisson":
            n = rng.poisson(float(law["mean"]), int(d))
        elif law["law"] == "lognormal":
            sigma = float(law["sigma"])
            mu = np.log(float(law["mean"])) - 0.5 * sigma * sigma
            n = np.round(rng.lognormal(mu, sigma, int(d)))
        else:
            raise ValueError(f"unknown tokens_per_doc law {law['law']!r}")
        out.append(np.maximum(n.astype(np.int64), int(law["min"])))
        by_size[int(d)] = out[-1]
    return out


def _topic_words(rng, spec: dict, z: np.ndarray) -> np.ndarray:
    """Draw one base word per token from its planted topic `z`."""
    v = int(spec["base_vocab"])
    words_law = spec["words"]
    k = int(spec["generator_topics"])
    n = z.shape[0]
    if words_law["law"] == "blocks":
        # The repository generator's planted topics: a topic puts
        # `block_mass` uniformly on its own block of V/K words and the rest
        # uniformly on the whole vocabulary. Each group gets its own
        # permutation of the vocabulary, so products differ.
        perm = rng.permutation(v)
        block = v // k
        in_block = rng.random(n) < float(words_law["block_mass"])
        w = rng.integers(0, v, n)
        pos = z * block + rng.integers(0, block, n)
        return np.where(in_block, perm[pos], w).astype(np.int32)
    if words_law["law"] == "zipf":
        # Zipf within topics: word ranks ∝ 1/(r+1)^s. A share of each
        # topic's mass follows one order of the vocabulary common to all
        # topics (the corpus-wide head of frequent words); the rest follows
        # the topic's own random order.
        s = float(words_law["s"])
        cdf = np.cumsum(1.0 / np.arange(1, v + 1) ** s)
        cdf /= cdf[-1]
        ranks = np.minimum(cdf.searchsorted(rng.random(n), side="right"),
                           v - 1)
        perms = np.argsort(rng.random((k, v)), axis=1)
        common = rng.random(n) < float(words_law["common_mass"])
        return np.where(common, ranks, perms[z, ranks]).astype(np.int32)
    raise ValueError(f"unknown words law {words_law['law']!r}")


def generate_group(spec: dict, lengths: np.ndarray,
                   rng: np.random.Generator) -> Group:
    """One group's reviews: lengths are given, everything else is drawn."""
    k = int(spec["generator_topics"])
    v = int(spec["base_vocab"])
    d = int(lengths.shape[0])
    lengths = lengths[rng.permutation(d)]
    n_users = int(spec["users_per_group"])
    n_neg = max(1, int(k * float(spec["negative_topic_frac"])))

    user_bias = rng.normal(0.0, 0.4, n_users)
    user = rng.integers(0, n_users, d)
    relevant = rng.random(d) > float(spec["irrelevant_frac"])
    sentiment = rng.uniform(1.0, 5.0, d)
    rating = np.clip(np.round(sentiment + user_bias[user]
                              + rng.normal(0, 0.3, d)), 1, 5)
    alpha = np.full((d, k), 0.3)
    negative = sentiment <= 2.5
    alpha[negative, k - n_neg:] += 3.0
    alpha[~negative, :k - n_neg] += 1.5
    theta = rng.standard_gamma(alpha)
    theta /= theta.sum(1, keepdims=True)

    counts = rng.multinomial(lengths, theta)  # (D, K) topic counts per doc
    z = np.repeat(np.tile(np.arange(k), d), counts.ravel())
    words = _topic_words(rng, spec, z)
    # Off-topic reviews draw every token uniformly.
    tok_rel = np.repeat(relevant, lengths)
    noise = rng.integers(0, v, words.shape[0]).astype(np.int32)
    tokens = np.where(tok_rel, words, noise).astype(np.int32)

    wq = np.clip(rng.normal(np.where(relevant, 0.6, 0.2), 0.15), 0, 1)
    votes = rng.poisson(6, d)
    helpful = np.round(votes * np.where(relevant, wq, wq * 0.4)).astype(
        np.int64)
    unhelpful = np.maximum(0, votes - helpful)
    return Group(tokens=tokens, doc_len=lengths, rating=rating, user=user,
                 helpful=helpful, unhelpful=unhelpful, writing_quality=wq)


def generate(spec: dict, seed: int) -> list[Group]:
    """All groups of a configuration's corpus for one run seed."""
    seqs = np.random.SeedSequence([int(seed) % 2**63, 0x76626e]).spawn(
        spec["groups"])
    return [generate_group(spec, n, np.random.default_rng(s))
            for n, s in zip(doc_lengths(spec), seqs)]

