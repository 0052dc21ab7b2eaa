"""The control at a test size: the reference computed in bfloat16 fails
the comparison that the float32 reference passes."""

import numpy as np

import check
import corpus
import reference

LIMITS = {"unanswered": 0, "malformed": 0, "dist_gap": 1e-4,
          "recall_miss": 0.1}


def _data(normalize):
    return corpus.make({"corpus_seed": 7, "points_per_cluster": 100,
                        "spread": 0.15,
                        "normalize": normalize}, 20000, 96, 256, 5)


def test_exact_reference_agrees_with_numpy_brute_force():
    data, q = _data(False)
    ids, d2 = reference.topk(data, q[:64], 10)
    full = ((q[:64, None, :].astype(np.float64) - data[None]) ** 2).sum(-1)
    want = np.argsort(full, axis=1, kind="stable")[:, :10]
    assert np.array_equal(ids, want)
    assert np.allclose(d2, np.take_along_axis(full, want, axis=1))


def test_bfloat16_control_fails_where_float32_passes():
    for normalize in (False, True):
        data, q = _data(normalize)
        exact, d2 = reference.topk(data, q, 10)
        ok = check.compare(data, q, list(zip(exact, d2)), exact, 10, LIMITS)
        assert check.passed(ok)
        assert ok["dist_gap"]["value"] < 1e-6
        low_ids, low_d = reference.topk(data, q, 10, precision="bfloat16")
        low = check.compare(data, q, list(zip(low_ids, low_d)), exact, 10,
                            LIMITS)
        assert not check.passed(low)
        assert low["dist_gap"]["value"] > 10 * LIMITS["dist_gap"]


def test_missing_and_malformed_answers_count():
    data, q = _data(False)
    exact, d2 = reference.topk(data, q[:4], 10)
    answers = [None, (exact[1][:9], d2[1][:9]),
               (exact[2][::-1], d2[2][::-1]), (exact[3], d2[3])]
    c = check.compare(data, q[:4], answers, exact, 10, LIMITS)
    assert c["unanswered"]["value"] == 1
    assert c["malformed"]["value"] == 2
    assert not check.passed(c)


def test_corpus_is_the_configurations_and_queries_are_the_seeds():
    cfg = {"corpus_seed": 7, "points_per_cluster": 100, "spread": 0.15}
    a_data, a_q = corpus.make(cfg, 2000, 16, 64, 1)
    b_data, b_q = corpus.make(cfg, 2000, 16, 64, 2**33 + 1)
    assert np.array_equal(a_data, b_data)
    assert not np.array_equal(a_q, b_q)
    assert np.array_equal(a_q, corpus.make(cfg, 2000, 16, 64, 1)[1])
