"""Jitted public wrappers for the Pallas kernels.

Off a TPU the kernels execute with interpret=True (the kernel body runs as
XLA-CPU ops); on a TPU they compile natively. ``interpret=None`` picks from
the backend.
"""
from __future__ import annotations

from functools import partial

import jax

from .cd_epoch import cd_epoch_gram_pallas, cd_epoch_xb_pallas
from .common import (UnsupportedPenaltyError, check_kernel_penalty,
                     check_score_kernel_penalty, make_penalty, penalty_params)
from .fused_ws import fused_ws_pallas
from .ws_score import ws_score_pallas


def _interpret_default():
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("penalty_cls", "epochs", "interpret"))
def cd_epoch_gram(G, c, beta0, q0, L, penalty_cls, params, *, epochs=1,
                  interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return cd_epoch_gram_pallas(G, c, beta0, q0, L, penalty_cls, params,
                                epochs=epochs, interpret=interpret)


@partial(jax.jit, static_argnames=("penalty_cls", "datafit_kind", "epochs",
                                   "interpret"))
def cd_epoch_xb(Xt_ws, y, beta0, Xb0, L, offset, penalty_cls, params,
                datafit_kind="quadratic", *, w=None, epochs=1,
                interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return cd_epoch_xb_pallas(Xt_ws, y, beta0, Xb0, L, offset, penalty_cls,
                              params, datafit_kind, w=w, epochs=epochs,
                              interpret=interpret)


@partial(jax.jit, static_argnames=("penalty_cls", "use_fp", "bp", "bn",
                                   "interpret"))
def ws_score(X, r, beta, L, offset, penalty_cls, params, *, w=None,
             use_fp=False, bp=256, bn=2048, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return ws_score_pallas(X, r, beta, L, offset, penalty_cls, params, w=w,
                           use_fp=use_fp, bp=bp, bn=bn, interpret=interpret)


@partial(jax.jit, static_argnames=("penalty_cls", "ws_size", "use_fp", "bp",
                                   "interpret"))
def fused_ws(X, r, beta, L, offset, gsupp, penalty_cls, params, ws_size, *,
             use_fp=False, bp=None, interpret=None):
    """Fused score + candidate top-k + candidate-column gather in one X
    traversal (see repro.kernels.fused_ws). Returns
    ``(scores, grad, cand_idx, cand_cols)``."""
    interpret = _interpret_default() if interpret is None else interpret
    return fused_ws_pallas(X, r, beta, L, offset, gsupp, penalty_cls, params,
                           ws_size, use_fp=use_fp, bp=bp, interpret=interpret)


__all__ = ["cd_epoch_gram", "cd_epoch_xb", "ws_score", "fused_ws",
           "penalty_params", "make_penalty", "check_kernel_penalty",
           "check_score_kernel_penalty", "UnsupportedPenaltyError"]
