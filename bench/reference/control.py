"""The control: the reference's KKT arithmetic, run on the device in the
nearest precision below the configuration's, in the program's place.

A configuration states float32 with matmuls at ``highest`` (the engine traces
every step under ``jax.default_matmul_precision("highest")``), so for a dense
design the control computes X b and X^T r in three bfloat16 passes, the
``high`` precision, spelled out so it rounds alike on every backend: each
float32 operand splits into a bfloat16 head and a bfloat16 tail, and the
product keeps head*head + head*tail + tail*head with float32 accumulation.
(Left to the compiler, a float32 -> bfloat16 -> float32 round trip may be
dropped on a TPU, which turns three passes into one.)
A CSC design's score is no matmul but elementwise float32 products summed
per column; its control takes those products in bfloat16.

Read at the answers the program returned, the control's KKT value lies
farther from the float64 value than the program's own certificate does: that
distance is what ``kkt_gap`` compares.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _split(a):
    """float32 -> (bfloat16 head, bfloat16 tail). The head is `a` with the
    low 16 bits of its float32 word cleared, so it converts to bfloat16
    exactly and `a - head` is exact: no float32 -> bfloat16 -> float32
    round trip that a compiler allowed excess precision could drop."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _dot3(a, b):
    """Three-pass product of split operands: a = (head, tail), b likewise."""
    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return d(a[0], b[0]) + d(a[0], b[1]) + d(a[1], b[0])


@jax.jit
def _dense_grad(X, Y, B, W):
    n = X.shape[0]
    Xs = _split(X)                      # split once, read for both products
    R = (_dot3(Xs, _split(B)) - Y) / n * W
    return _dot3(_split(R.T), Xs).T


@jax.jit
def _csc_grad(vals, rows, cols, Y, B, W):
    n = Y.shape[0]
    v = vals.astype(jnp.bfloat16)
    XB = jax.ops.segment_sum(
        (v[:, None] * B[cols].astype(jnp.bfloat16)).astype(jnp.float32),
        rows, num_segments=n)
    R = ((XB - Y) / n * W).astype(jnp.bfloat16)
    return jax.ops.segment_sum(
        (v[:, None] * R[rows]).astype(jnp.float32), cols,
        num_segments=B.shape[0])


def gradients(X, Y, B, W=None):
    """Lower-precision g = X^T (w * (X b - y)) / n on the device: [p, S].

    X: a dense device array, or a scipy CSC matrix (copied to the device)."""
    Y = jnp.asarray(Y, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    W = jnp.ones_like(Y) if W is None else jnp.asarray(W, jnp.float32)
    if hasattr(X, "tocsc"):
        X = X.tocsc()
        cols = np.repeat(np.arange(X.shape[1], dtype=np.int32),
                         np.diff(X.indptr))
        return _csc_grad(jnp.asarray(X.data, jnp.float32),
                         jnp.asarray(X.indices, jnp.int32),
                         jnp.asarray(cols), Y, B, W)
    return _dense_grad(jnp.asarray(X, jnp.float32), Y, B, W)


def kkt(X, Y, B, lams, W=None):
    """The control's KKT value of each answer: [S], as float64 numpy."""
    from .kkt import l1_dist
    G = np.asarray(gradients(X, Y, B, W), np.float64)
    B = np.asarray(B, np.float64)
    return l1_dist(G, B, np.asarray(lams, np.float64)[None, :]).max(axis=0)
