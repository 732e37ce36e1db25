"""The plain references, checked against cruder ones that share nothing
with them or with the code under test."""

import numpy as np

from benchmark import traffic
from benchmark.reference import collective, ffm, gbdt


def test_root_gains_against_a_loop_over_candidates():
    bins, y = traffic.binned_table(1, 3_000, 4, 8)
    gain = gbdt.root_gains(bins, y, 8, reg_lambda=1.0)
    g = 0.5 - y.astype(np.float64)
    for f in range(4):
        for b in range(7):
            left = bins[:, f] <= b
            gl, hl = g[left].sum(), 0.25 * left.sum()
            gr, hr = g[~left].sum(), 0.25 * (~left).sum()
            want = (gl * gl / (hl + 1) + gr * gr / (hr + 1)
                    - g.sum() ** 2 / (0.25 * len(y) + 1))
            assert np.isclose(gain[f, b], want, rtol=1e-12)
    assert np.isneginf(gain[:, -1]).all()
    f, b = np.unravel_index(np.argmax(gain), gain.shape)
    assert gbdt.root_split_ok(gain, f, b)
    worst = np.unravel_index(np.argmin(gain[:, :-1]), (4, 7))
    assert not gbdt.root_split_ok(gain, *worst)


def test_route_margins_by_hand():
    # depth 2: root splits feature 0 at bin 3; left child feature 1 at 1,
    # right child feature 1 at 5; leaves 1, 2, 3, 4
    tree = (np.array([0, 1, 1]), np.array([3, 1, 5]), np.zeros(3, int),
            np.array([1.0, 2.0, 3.0, 4.0], np.float32))
    bins = np.array([[0, 0], [3, 2], [4, 5], [7, 6]], np.int32)
    got = gbdt.route_margins([tree, tree], bins, depth=2, learning_rate=0.5)
    assert np.allclose(got, [1.0, 2.0, 3.0, 4.0])      # 2 trees x 0.5
    assert np.isclose(gbdt.logloss(np.zeros(4), np.array([0, 1, 0, 1.0])),
                      np.log(2.0))


def _ffm_loss(table, w, w0, feats, fields, vals, y, n_fields):
    """FFM logloss straight from the paper's formula, loops and all."""
    total = 0.0
    for n in range(feats.shape[0]):
        z = w0 + sum(w[feats[n, a]] * vals[n, a]
                     for a in range(feats.shape[1]))
        for a in range(feats.shape[1]):
            for b in range(a + 1, feats.shape[1]):
                va = table[feats[n, a] * n_fields + fields[n, b]]
                vb = table[feats[n, b] * n_fields + fields[n, a]]
                z += float(va @ vb) * vals[n, a] * vals[n, b]
        total += max(z, 0) - z * y[n] + np.log1p(np.exp(-abs(z)))
    return total / feats.shape[0]


def test_ffm_step_is_one_sgd_step_of_the_papers_loss():
    rng = np.random.default_rng(0)
    n_fields, per, k, lr = 3, 4, 2, 0.1
    pool = traffic.zipf_chunk_pool(5, n_fields * per, n_fields, 6, 1,
                                   1.1, 0.5)
    feats, fields, vals, y = pool[0]
    vals = rng.random(vals.shape) + 0.5
    table = rng.standard_normal((n_fields * per * n_fields, k)) * 0.3
    w = rng.standard_normal(n_fields * per) * 0.1
    w0 = 0.05
    rows = feats[:, :, None] * n_fields + fields[:, None, :]
    loss, new_w0, uniq, new_rows, ufeat, new_w = ffm.step(
        table[rows], w[feats], w0, rows, feats, vals, y, lr)
    assert np.isclose(loss, _ffm_loss(table, w, w0, feats, fields, vals, y,
                                      n_fields), rtol=1e-12)

    def numeric(param, index):
        eps = 1e-6
        out = []
        for sign in (1, -1):
            t, ww, b = table.copy(), w.copy(), w0
            if param == "table":
                t[index] += sign * eps
            elif param == "w":
                ww[index] += sign * eps
            else:
                b += sign * eps
            out.append(_ffm_loss(t, ww, b, feats, fields, vals, y, n_fields))
        return (out[0] - out[1]) / (2 * eps)

    for i in (0, len(uniq) // 2, len(uniq) - 1):
        for c in range(k):
            want = table[uniq[i], c] - lr * numeric("table", (uniq[i], c))
            assert np.isclose(new_rows[i, c], want, rtol=1e-5, atol=1e-9)
    for i in (0, len(ufeat) - 1):
        want = w[ufeat[i]] - lr * numeric("w", ufeat[i])
        assert np.isclose(new_w[i], want, rtol=1e-5, atol=1e-9)
    assert np.isclose(new_w0, w0 - lr * numeric("w0", None), rtol=1e-5)


def test_mean_of_ranks_is_the_sum_over_ranks_divided():
    idx = np.arange(1000)
    want = sum(traffic.small_ints(np, idx.astype(np.uint32), r, 4)
               for r in range(4)) / 4
    got = collective.mean_of_ranks(idx, 4, 4)
    assert got.dtype == np.float32 and np.array_equal(got, want)
    assert got.max() <= 7 and got.any()
