"""Harness arithmetic, generators and the data that names every piece."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import _paths
from vbench import corpus, harness

BENCH = harness.load_json(_paths.CHECKOUT, "BENCHMARK.json")


def _spec(**over):
    spec = harness.load_json(harness.HERE, "configs",
                             "amazon-products.json")["corpus"]
    spec = dict(spec, groups=6, base_vocab=300, **over)
    return spec


def test_traffic_is_deterministic_from_seed():
    a = corpus.generate(_spec(), 12345678901)
    b = corpus.generate(_spec(), 12345678901)
    c = corpus.generate(_spec(), 7)
    for x, y in zip(a, b):
        assert np.array_equal(x.tokens, y.tokens)
        assert np.array_equal(x.rating, y.rating)
    # Another seed: the same shapes (so the same compiled programs), other
    # content and another order of the document lengths.
    assert [g.num_tokens for g in a] == [g.num_tokens for g in c]
    assert [sorted(g.doc_len) for g in a] == [sorted(g.doc_len) for g in c]
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))


def test_product_sizes_follow_the_stated_power_law():
    spec = harness.load_json(harness.HERE, "configs",
                             "amazon-products.json")["corpus"]
    sizes = corpus.group_sizes(spec)
    assert len(sizes) == 128
    assert sizes.min() >= 50 and sizes.max() <= 5000
    # p(n) ~ n^-2 on [50, 5000] has mean ln(100) / (1/50 - 1/5000) = 232.6.
    assert sizes.mean() == pytest.approx(232.6, rel=0.05)
    # Log-spaced classes of a 1/n^2 law: each class holds about 1/1.58 of
    # the products of the class below it (at least one).
    values, counts = np.unique(sizes, return_counts=True)
    assert len(values) == spec["docs_per_group"]["classes"]
    assert np.all(np.diff(counts) <= 0)
    assert counts[0] / counts[1] == pytest.approx(10 ** 0.2, rel=0.2)
    # Tokens per review: Poisson(60), floor 5; one set of lengths per class.
    lengths = corpus.doc_lengths(spec)
    assert len({(len(x), int(x.sum())) for x in lengths}) == len(values)
    flat = np.concatenate(lengths)
    assert flat.min() >= 5
    assert flat.mean() == pytest.approx(60, rel=0.02)


def test_zipf_words_shape():
    spec = harness.load_json(harness.HERE, "configs", "nytimes.json")[
        "corpus"]
    spec = dict(spec, docs_per_group={"law": "fixed", "value": 60})
    g = corpus.generate(spec, 3)[0]
    counts = np.sort(np.bincount(g.tokens))[::-1]
    # Heavy head: the 100 most frequent words carry a large share.
    assert counts[:100].sum() > 0.2 * counts.sum()
    assert g.tokens.max() < spec["base_vocab"]


def _run_with(requests, window=(10.0, 30.0)):
    cell = harness.find_cell("amazon.refit")
    run = harness.Run(cell=cell, seed=1, seconds=20.0, trace=False,
                      t_start=0.0)
    run.requests = requests
    run.window = window
    return run


def test_rate_is_all_work_over_the_whole_window():
    reqs = [harness.Request("refine_batch", 10.0 + i, 11.0 + i, True,
                            1000.0, None) for i in range(20)]
    reqs.append(harness.Request("refine_batch", 30.0, ok=False,
                                token_sweeps=5000.0))
    run = _run_with(reqs)
    got = harness.load_module("metrics", "fit_tokens_per_s").read(run)
    # 20 completed requests of 1000 token-sweeps over 20 s; the failed one
    # adds no work, and the window is not cut short to the last request.
    assert got == pytest.approx(1000.0)


def test_every_cell_and_metric_is_found_by_name():
    names = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for m in names:
        assert callable(harness.metric_reader(m).read)
    for w in BENCH["workloads"]:
        cell = harness.find_cell(w["name"], BENCH)
        assert cell.config["name"] == w["config"]
        assert harness.load_module("references", cell.config["reference"])
        assert harness.load_module("routes", cell.traffic["route"]["backend"])
        verbs = [s["verb"] for s in cell.traffic["setup"]] + [
            cell.traffic["warmup"]["verb"], cell.traffic["request"]["verb"]]
        for v in verbs:
            assert harness.load_module("verbs", v)
        assert {"count_err", "move_gap"} <= set(cell.limits) <= {
            "count_err", "move_gap", "calib_gap"}
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(_paths.CHECKOUT, c["file"]))


def test_split_metric_names_share_one_reader():
    shared = harness.load_module("metrics", "fit_mfu")
    assert harness.metric_reader("fit_mfu.refit") is shared
    assert harness.metric_reader("fit_mfu.corpus") is shared
    assert harness.metric_reader("fit_tokens_per_s.corpus") is \
        harness.load_module("metrics", "fit_tokens_per_s")
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_quantity.refit")


def test_alias_reference_follows_the_mh_acceptance():
    """One sweep of the alias reference from a fixed state moves tokens as
    often as the exact expectation of its MH rounds says (a chain that
    accepted every proposal, or none, would not)."""
    import jax

    rf = harness.load_module("references", "rlda_alias")
    rng = np.random.default_rng(0)
    n, k, nd, v = 4000, 8, 40, 50
    flat = rf.Flat(docs=np.sort(rng.integers(0, nd, n)).astype(np.int32),
                   words=rng.integers(0, v, n).astype(np.int64),
                   model=np.zeros(n, np.int32),
                   weights=np.ones(n, np.float32),
                   doc_offset=np.array([0, nd]), vocab=v, num_models=1)
    model = {"num_topics": k, "alpha": 0.1, "beta": 0.01, "mh_steps": 1}
    shape, corp = rf.device_corpus(flat, model)
    z0 = rng.integers(0, k, n).astype(np.int32)
    moved = np.mean([np.mean(np.asarray(rf.chain(
        shape, corp, rf.pad_z(shape, z0), jax.random.PRNGKey(s), 1)[0])[:n]
        != z0) for s in range(8)])
    # Exact: with one word-proposal round, P(move) = sum_t q(t) min(1, ...)
    # over t != z, from the counts of z0.
    n_dt, n_wt, n_t = rf.exact_counts(flat, z0, k)
    d, w = flat.docs, flat.words
    own = np.eye(k)[z0]
    p = ((np.maximum(n_dt[d] - own, 0) + 0.1)
         * (np.maximum(n_wt[w] - own, 0) + 0.01)
         / (np.maximum(n_t[0][None] - own, 1e-9) + 0.01 * v))
    q = n_wt[w] + 0.01
    q = q / q.sum(1, keepdims=True)
    ps, qs = p[np.arange(n), z0], q[np.arange(n), z0]
    acc = np.minimum(1.0, p * qs[:, None] / (ps[:, None] * q))
    want = np.mean(np.sum(np.where(own > 0, 0.0, q * acc), axis=1))
    assert moved == pytest.approx(want, abs=0.01)


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(_paths.CHECKOUT, "vbench", "run.py"),
         "--workload", w, "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"],
        cwd=_paths.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "nothing was run" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
