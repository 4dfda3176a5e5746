"""Plain reference of RLDA collapsed Gibbs sampling (paper §3.1, §4.3).

Written from the paper and the configuration alone; it imports nothing of
the program. It has three parts:

- `prepare`: the §4.3 transformation of reviews into weighted tokens over
  the rating-augmented vocabulary (word * 5 + tier), with the review-quality
  weight psi and the tier probability as each token's weight. The quality
  logistic and the tier probabilities are computed in float32 on the
  accelerator, as the configuration states; user rating biases in float64.
- `exact_counts`: the doc-topic, word-topic and topic counts that a state's
  assignments imply, summed in float64.
- `chain`: the parallel collapsed-Gibbs sweep (every token resampled against
  the counts at the start of the sweep, its own weight excluded, by
  Gumbel-max), `sweeps` times, with counts rebuilt after each sweep, on the
  accelerator in blocks of tokens; and `mean_log_conditional`, the average
  log-probability of each token's assignment under its exact conditional.

Many models (a catalog of products) are handled as one flat corpus: model m's
documents and words are offset into disjoint ranges, and topic totals are
kept per model.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NUM_TIERS = 5
TIER_EDGES = np.array([1.5, 2.5, 3.5, 4.5])
# The review-quality logistic's default weights for (writing quality,
# log1p unhelpful, log1p helpful) and its bias (paper §4.3, no labels).
QUALITY_W = (1.5, -1.0, 1.0)
QUALITY_B = 1.0


@dataclasses.dataclass
class Prepared:
    """One model's weighted tokens."""

    docs: np.ndarray  # (N,) int32
    words: np.ndarray  # (N,) int32 augmented word ids
    weights: np.ndarray  # (N,) float32
    num_docs: int
    vocab: int  # augmented vocabulary size


def _user_bias(ratings: np.ndarray, users: np.ndarray):
    """Leave-one-out mean and variance of each review's user bias."""
    bias = ratings - (ratings.mean() if len(ratings) else 0.0)
    nu = users.max() + 1 if len(users) else 0
    cnt = np.bincount(users, minlength=nu).astype(np.float64)
    s1 = np.bincount(users, weights=bias, minlength=nu)
    s2 = np.bincount(users, weights=bias ** 2, minlength=nu)
    b = np.zeros_like(ratings)
    v = np.zeros_like(ratings)
    for i, u in enumerate(users):
        n = cnt[u] - 1.0
        if n >= 1.0:
            m = (s1[u] - bias[i]) / n
            b[i] = m
            if n >= 2.0:
                v[i] = max((s2[u] - bias[i] ** 2) / n - m ** 2, 0.0) \
                    * n / (n - 1.0)
    return b, v, cnt[users] > 1.5


def _tier_probs(r, b, s2):
    """P(bias-corrected rating in each tier), float32."""
    mu = r + b
    sd = jnp.sqrt(s2 + 1.0)
    edges = jnp.asarray(TIER_EDGES)
    x = (edges[None, :] - mu[:, None]) / sd[:, None]
    cdf = 0.5 * (1.0 + jax.scipy.special.erf(x / np.sqrt(2.0)))
    ones = jnp.ones_like(mu)[:, None]
    zeros = jnp.zeros_like(mu)[:, None]
    return jnp.concatenate([cdf, ones], axis=1) - jnp.concatenate(
        [zeros, cdf], axis=1)


def _quality(nu, u, h):
    x = jnp.stack([jnp.asarray(nu, jnp.float32),
                   jnp.log1p(jnp.asarray(u, jnp.float32)),
                   jnp.log1p(jnp.asarray(h, jnp.float32))], axis=-1)
    x = (x - jnp.zeros(3)) / jnp.ones(3)
    return jax.nn.sigmoid(x @ jnp.array(QUALITY_W) + jnp.array(QUALITY_B))


def prepare(group, base_vocab: int) -> Prepared:
    """Weighted augmented tokens of one group of reviews (paper §4.3)."""
    ratings = np.asarray(group.rating, np.float64)
    users = np.asarray(group.user, np.int64)
    psi = np.asarray(_quality(
        np.asarray(group.writing_quality, np.float64),
        np.asarray(group.unhelpful, np.float64),
        np.asarray(group.helpful, np.float64)), np.float64)
    b, v, hist = _user_bias(ratings, users)
    cprob = np.asarray(_tier_probs(jnp.asarray(ratings), jnp.asarray(b),
                                   jnp.asarray(v)))
    obs = np.clip(np.round(ratings) - 1, 0, NUM_TIERS - 1).astype(np.int64)
    tier = np.where(hist, np.argmax(cprob, axis=1), obs)
    tier_w = np.where(hist, cprob[np.arange(len(ratings)), tier], 1.0)
    doc = group.doc_of_token
    words = np.asarray(group.tokens, np.int64) * NUM_TIERS + tier[doc]
    weights = (psi * tier_w)[doc].astype(np.float32)
    return Prepared(docs=doc.astype(np.int32), words=words.astype(np.int32),
                    weights=weights, num_docs=group.num_docs,
                    vocab=base_vocab * NUM_TIERS)


@dataclasses.dataclass
class Flat:
    """Several models' tokens as one corpus with disjoint id ranges."""

    docs: np.ndarray  # (N,) int32 global doc ids
    words: np.ndarray  # (N,) int64 global word ids (model * V + word)
    model: np.ndarray  # (N,) int32
    weights: np.ndarray  # (N,) float32
    doc_offset: np.ndarray  # (M+1,) first global doc of each model
    vocab: int
    num_models: int

    @property
    def num_docs(self) -> int:
        return int(self.doc_offset[-1])


def flatten(preps: list[Prepared]) -> Flat:
    doc_off = np.concatenate([[0], np.cumsum([p.num_docs for p in preps])])
    vocab = preps[0].vocab
    return Flat(
        docs=np.concatenate([p.docs + doc_off[i]
                             for i, p in enumerate(preps)]).astype(np.int32),
        words=np.concatenate([p.words.astype(np.int64) + i * vocab
                              for i, p in enumerate(preps)]),
        model=np.concatenate([np.full(len(p.docs), i, np.int32)
                              for i, p in enumerate(preps)]),
        weights=np.concatenate([p.weights for p in preps]),
        doc_offset=doc_off, vocab=vocab,
        num_models=len(preps))


def exact_counts(flat: Flat, z: np.ndarray, k: int):
    """(n_dt (D, K), n_wt (M*V, K), n_t (M, K)) in float64."""
    w = flat.weights.astype(np.float64)
    z = np.asarray(z, np.int64)
    n_dt = np.bincount(flat.docs.astype(np.int64) * k + z, weights=w,
                       minlength=flat.num_docs * k).reshape(-1, k)
    n_wt = np.bincount(flat.words * k + z, weights=w,
                       minlength=flat.num_models * flat.vocab * k
                       ).reshape(-1, k)
    n_t = np.bincount(flat.model.astype(np.int64) * k + z, weights=w,
                      minlength=flat.num_models * k).reshape(-1, k)
    return n_dt, n_wt, n_t


def count_error(program, exact) -> float:
    """Largest gap between a program count and the exact count, relative to
    the exact count or to one token, whichever is larger."""
    worst = 0.0
    for got, want in zip(program, exact):
        got = np.asarray(got, np.float64)
        gap = np.abs(got - want) / np.maximum(want, 1.0)
        worst = max(worst, float(gap.max()) if gap.size else 0.0)
    return worst


# -- the sampler on the accelerator ------------------------------------------


def _block(k: int) -> int:
    """Tokens per block so that a (block, K) float32 array stays ~64 MiB."""
    return int(max(1024, min(65536, 2 ** 24 // k)))


@dataclasses.dataclass(frozen=True)
class Shape:
    k: int
    num_docs: int
    num_words: int  # M * V
    num_models: int
    n_pad: int
    block: int
    alpha: float
    beta: float
    beta_bar: float  # V * beta of one model
    mh_steps: int = 0  # Metropolis-Hastings rounds a sweep (alias chain)


def device_corpus(flat: Flat, model: dict):
    """Pad the flat corpus to whole blocks and put it on the device, with
    the sizes the configuration's `model` group states."""
    k = int(model["num_topics"])
    alpha, beta = float(model["alpha"]), float(model["beta"])
    n = len(flat.docs)
    blk = _block(k)
    n_pad = -(-n // blk) * blk
    pad = n_pad - n

    def p(x, dtype):
        return jnp.asarray(np.pad(x.astype(dtype), (0, pad)))

    shape = Shape(k=k, num_docs=flat.num_docs,
                  num_words=flat.num_models * flat.vocab,
                  num_models=flat.num_models, n_pad=n_pad, block=blk,
                  alpha=alpha, beta=beta, beta_bar=beta * flat.vocab,
                  mh_steps=int(model.get("mh_steps", 0)))
    # Padding tokens carry weight 0: they add nothing to any count.
    return shape, (p(flat.docs, np.int32), p(flat.words, np.int32),
                   p(flat.model, np.int32), p(flat.weights, np.float32))


def _counts(shape: Shape, corpus, z, dtype):
    docs, words, model, w = corpus
    w = w.astype(dtype)
    n_dt = jnp.zeros((shape.num_docs, shape.k), dtype).at[docs, z].add(w)
    n_wt = jnp.zeros((shape.num_words, shape.k), dtype).at[words, z].add(w)
    n_t = jnp.zeros((shape.num_models, shape.k), dtype).at[model, z].add(w)
    return n_dt, n_wt, n_t


def _log_cond(shape: Shape, counts, d, wd, m, wt, z, dtype):
    """(block, K) log of the unnormalised exact conditional, own token out."""
    n_dt, n_wt, n_t = counts
    own = jax.nn.one_hot(z, shape.k, dtype=dtype) * wt.astype(dtype)[:, None]
    rd = jnp.maximum(n_dt[d] - own, 0)
    rw = jnp.maximum(n_wt[wd] - own, 0)
    rt = jnp.maximum(n_t[m] - own, 1e-9)
    a = jnp.asarray(shape.alpha, dtype)
    b = jnp.asarray(shape.beta, dtype)
    bb = jnp.asarray(shape.beta_bar, dtype)
    return jnp.log(rd + a) + jnp.log(rw + b) - jnp.log(rt + bb)


def _blocks(shape: Shape, *xs):
    return tuple(x.reshape(shape.n_pad // shape.block, shape.block)
                 for x in xs)


@partial(jax.jit, static_argnums=(0, 4, 5))
def chain(shape: Shape, corpus, z, key, sweeps: int, dtype=jnp.float32):
    """`sweeps` parallel Gibbs sweeps from assignments `z`.

    Returns the last assignments and the counts the chain built from them
    in `dtype` (the control runs the whole chain, counts included, in a
    lower precision)."""
    docs, words, model, w = corpus

    def sweep(i, z):
        counts = _counts(shape, corpus, z, dtype)
        keys = jax.random.split(jax.random.fold_in(key, i),
                                shape.n_pad // shape.block)

        def body(args):
            d, wd, m, wt, zb, kb = args
            lg = _log_cond(shape, counts, d, wd, m, wt, zb, dtype)
            g = jax.random.gumbel(kb, lg.shape, jnp.float32).astype(dtype)
            new = jnp.argmax(lg + g, axis=-1).astype(jnp.int32)
            return jnp.where(wt > 0, new, zb)

        return jax.lax.map(body, _blocks(shape, docs, words, model, w, z)
                           + (keys,)).reshape(-1)

    z = jax.lax.fori_loop(0, sweeps, sweep, z)
    return z, _counts(shape, corpus, z, dtype)


@partial(jax.jit, static_argnums=(0,))
def mean_log_conditional(shape: Shape, corpus, z):
    """Weight-free mean over real tokens of log p(z_i | all other tokens)."""
    docs, words, model, w = corpus
    counts = _counts(shape, corpus, z, jnp.float32)

    def body(args):
        d, wd, m, wt, zb = args
        lg = _log_cond(shape, counts, d, wd, m, wt, zb, jnp.float32)
        lp = jnp.take_along_axis(lg, zb[:, None], axis=1)[:, 0] \
            - jax.nn.logsumexp(lg, axis=1)
        real = wt > 0
        return jnp.sum(jnp.where(real, lp, 0.0)), jnp.sum(real)

    s, n = jax.lax.map(body, _blocks(shape, docs, words, model, w, z))
    return jnp.sum(s) / jnp.sum(n)


def pad_z(shape: Shape, z: np.ndarray):
    return jnp.asarray(np.pad(np.asarray(z, np.int32),
                              (0, shape.n_pad - len(z))))


# -- the comparison that decides `correct` -----------------------------------


#: `count_err` of assignments outside [0, K) (finite, so the result line
#: stays valid JSON).
OUT_OF_RANGE = 1e30


def _fixed_scale(config: dict) -> float:
    """Stored counts are integers in units of 2^-(w_bits+1) of a token
    (paper §4.3); float counts when w_bits is null."""
    wb = config["model"]["w_bits"]
    return 1.0 if wb is None else float(2 ** (int(wb) + 1))


def _limit(run, name: str) -> float:
    return float(run.cell.limits[name])


def check(run) -> list:
    """While the program's state lives: fetch the answers to compare, and
    compare the served counts with the counts their assignments imply."""
    import jax

    cfg = run.cell.config
    k = int(cfg["model"]["num_topics"])
    first = run.notes["first_answer"]
    last = len(run.answers) - 1
    if last < first:
        run.notes["ref"] = None
        return [{"name": "requests_checked", "value": 1.0, "limit": 0.0}]
    rng = run.rng(11)
    pick = [int(rng.integers(first, last))] if last > first else []
    samples = [(a, a - 1) for a in pick + [last]]
    fetch = {i for a, b in samples for i in (a, b)}
    zs = {i: np.concatenate([np.asarray(z) for z in jax.device_get(
        run.answers[i])]) for i in sorted(fetch)}
    hs = run.service.handles
    scale = _fixed_scale(cfg)
    states = jax.device_get([hs[h].state for h in run.handles])
    program = (np.concatenate([np.asarray(s.n_dt) for s in states]) / scale,
               np.concatenate([np.asarray(s.n_wt) for s in states]) / scale,
               np.stack([np.asarray(s.n_t) for s in states]) / scale)
    run.answers = []
    base_vocab = int(cfg["corpus"]["base_vocab"])
    flat = flatten([prepare(g, base_vocab) for g in run.groups])
    z_last = zs[last]
    if np.any((z_last < 0) | (z_last >= k)):
        err = OUT_OF_RANGE  # assignments outside [0, K): no count can match
    else:
        err = count_error(program, exact_counts(flat, z_last, k))
    run.notes["ref"] = dict(flat=flat, samples=[
        (zs[b], zs[a]) for a, b in samples])
    return [{"name": "count_err", "value": err,
             "limit": _limit(run, "count_err")}]


def compare(run, chain_fn=None) -> list:
    """After the program's state is freed: run the reference chain
    (`chain_fn`, this module's exact Gibbs chain by default) from the state
    each sampled request started from, for as many sweeps, and compare how
    far the program's and the reference's chains moved and how likely their
    assignments are under the exact conditionals."""
    import jax

    ref = run.notes.get("ref")
    if not ref:
        return []
    sweeps = int(run.cell.traffic["request"]["sweeps"])
    flat = ref["flat"]
    shape, corp = device_corpus(flat, run.cell.config["model"])
    move_gap, calib_gap = 0.0, 0.0
    for s, (z0, z1) in enumerate(ref["samples"]):
        key = jax.random.PRNGKey(run.derive(13, s))
        got = chain_numbers(shape, corp, z0, z1, sweeps, key, chain_fn)
        move_gap = max(move_gap, got["move_gap"])
        calib_gap = max(calib_gap, got["calib_gap"])
        run.notes.setdefault("readings", []).append(got)
    # A cell compares the numbers its limits file names; the others are
    # readings only.
    got = {"move_gap": move_gap, "calib_gap": calib_gap}
    return [{"name": k, "value": v, "limit": _limit(run, k)}
            for k, v in got.items() if k in run.cell.limits]


def chain_numbers(shape: Shape, corp, z0, z1, sweeps: int, key,
                  chain_fn=None) -> dict:
    """Compare an answer `z1` to the request that started from `z0` with
    the reference chain (`chain_fn`, exact Gibbs by default) run from `z0`
    for as many sweeps:

    - move_gap: |share of tokens the answer moved - share the reference
      moved| / share the reference moved;
    - calib_gap: |mean log exact conditional of the answer's assignments -
      that of the reference's|.
    """
    n = len(z0)
    z_ref, _ = (chain_fn or chain)(shape, corp, pad_z(shape, z0), key, sweeps)
    z_ref = np.asarray(z_ref)[:n]
    moved_p = float(np.mean(z1 != z0))
    moved_r = float(np.mean(z_ref != z0))
    lp = float(mean_log_conditional(shape, corp, pad_z(shape, z1)))
    lr = float(mean_log_conditional(shape, corp, pad_z(shape, z_ref)))
    return dict(move_gap=abs(moved_p - moved_r) / max(moved_r, 1e-9),
                calib_gap=abs(lp - lr), moved_program=moved_p,
                moved_reference=moved_r, loglik_program=lp,
                loglik_reference=lr)
