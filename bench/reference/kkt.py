"""Float64 KKT check of Lasso answers, on the host.

The arithmetic of ``chip_smoke.py``'s ``host_kkt`` / ``_l1_dist``: for the
quadratic datafit (1/2n) sum_i w_i (y_i - x_i b)^2 (w = 1 unweighted, or a
fold's train weights rescaled to sum to n) and the penalty lam ||b||_1, the
gradient is g = X^T (w * (X b - y)) / n and the violation of coordinate j is
the distance of -g_j to lam d|b_j|. An answer's KKT value is the largest
violation over j.

Many answers are checked in one pass over X: the design is read in row
blocks, cast to float64 block by block, so a dense design need not be held
twice in float64.
"""
from __future__ import annotations

import numpy as np

ROW_BLOCK = 1024


def l1_dist(g, b, lam):
    """dist(-g, lam * d|b|), per coordinate (broadcasts over answers)."""
    return np.where(b == 0, np.maximum(np.abs(g) - lam, 0.0),
                    np.abs(g + lam * np.sign(b)))


def gradients(X, Y, B, W=None):
    """g[:, s] = X^T (w_s * (X b_s - y_s)) / n in float64.

    X: [n, p] numpy (any float dtype) or scipy sparse; Y: [n, S] targets;
    B: [p, S] coefficients; W: [n, S] weights rescaled to sum to n, or None.
    """
    n = X.shape[0]
    B = np.asarray(B, np.float64)
    Y = np.asarray(Y, np.float64)
    if hasattr(X, "tocsc"):
        X64 = X.astype(np.float64)
        R = (X64 @ B - Y) / n
        if W is not None:
            R *= W
        return np.asarray(X64.T @ R)
    G = np.zeros(B.shape, np.float64)
    for i in range(0, n, ROW_BLOCK):
        Xi = np.asarray(X[i:i + ROW_BLOCK], np.float64)
        R = (Xi @ B - Y[i:i + ROW_BLOCK]) / n
        if W is not None:
            R *= W[i:i + ROW_BLOCK]
        G += Xi.T @ R
    return G


def kkt(X, Y, B, lams, W=None):
    """Float64 KKT value of each answer: [S]."""
    B = np.asarray(B, np.float64)
    G = gradients(X, Y, B, W)
    return l1_dist(G, B, np.asarray(lams, np.float64)[None, :]).max(axis=0)


def fold_train_weights(n, n_folds, seed_words):
    """[n_folds, n] 0/1 train membership of a shuffled k-fold split: the
    permutation is drawn from `seed_words`, held-out rows of fold f are the
    f-th of `n_folds` near-equal slices of it."""
    idx = np.random.default_rng(seed_words).permutation(n)
    W = np.ones((n_folds, n))
    for f, test in enumerate(np.array_split(idx, n_folds)):
        W[f, test] = 0.0
    return W
