"""Working-set machinery (paper Algorithm 1).

Features are ranked by violation of the first-order optimality condition
score_j = dist(-grad_j f(beta), d g_j(beta_j)) (Eq. 2), or by the fixed-point
violation score^cd (Appendix C, Eq. 24) when the penalty's subdifferential is
uninformative (l_q with 0<q<1). The working set grows as
ws_size = max(ws_size, 2 |gsupp(beta)|), taking the ws_size highest scores while
always retaining the current generalized support (scored +inf).

JAX adaptation: working sets are static-size (rounded up to powers of two) so
the jitted fused outer step is compiled once per size *bucket*, not per
iteration — BucketPolicy (DESIGN.md §3.2) makes the bucketing rule explicit
and enumerable, and the engine keeps a per-bucket retrace counter proving one
compile per bucket across a whole regularization path.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.bucketing import next_pow2
from repro.obs import collectives


def fixed_point_score(penalty, beta, grad, L):
    """score^cd_j = |beta_j - prox_{g_j/L_j}(beta_j - grad_j / L_j)| (Eq. 24)."""
    step = 1.0 / jnp.maximum(L, 1e-30)
    if beta.ndim == 2:
        step_b = step[:, None]
    else:
        step_b = step
    prox = penalty.prox(beta - grad * step_b, step_b)
    diff = beta - prox
    if beta.ndim == 2:
        return jnp.sqrt(jnp.sum(diff ** 2, axis=-1))
    return jnp.abs(diff)


def violation_scores(penalty, beta, grad, L, use_fixed_point=None):
    """Per-feature priority scores; picks score^d or score^cd automatically."""
    if use_fixed_point is None:
        use_fixed_point = not penalty.HAS_SUBDIFF
    if use_fixed_point:
        return fixed_point_score(penalty, beta, grad, L)
    return penalty.subdiff_dist(grad, beta)


def grow_ws_size(prev_size: int, gsupp_count: int, p: int, p0: int = 64,
                 growth: int = 2) -> int:
    """ws_size = max(prev, growth*|gsupp|), pow2-padded, clamped to p
    (static shapes; growth=2 is the paper's Algorithm 1 line 3)."""
    target = max(p0, prev_size, growth * gsupp_count)
    return min(p, next_pow2(target))


@dataclass(frozen=True)
class BucketPolicy:
    """Explicit working-set bucket policy (DESIGN.md §3.2).

    Buckets are the only retrace axis of the fused outer step: every outer
    iteration runs at a bucket from `ladder(p)` (powers of two from p0,
    clamped to p), chosen monotonically by `next_bucket`. A solve or a whole
    regularization path therefore compiles at most `len(ladder(p))` programs.
    """
    p0: int = 64
    growth: int = 2                  # bucket >= growth * |generalized support|

    def first_bucket(self, gsupp_count: int, p: int) -> int:
        return grow_ws_size(0, gsupp_count, p, p0=self.p0,
                            growth=self.growth)

    def next_bucket(self, prev: int, gsupp_count: int, p: int) -> int:
        return grow_ws_size(prev, gsupp_count, p, p0=self.p0,
                            growth=self.growth)

    def escalate(self, bucket: int, p: int) -> int:
        """Next rung of the ladder (chunked path: bucket too small)."""
        return min(p, next_pow2(bucket + 1))

    def ladder(self, p: int):
        """All buckets this policy can ever select for a p-feature problem."""
        out, b = [], min(p, next_pow2(self.p0))
        while b < p:
            out.append(b)
            b = next_pow2(b + 1)
        out.append(p)
        return out


def select_working_set(scores, gsupp_mask, ws_size: int):
    """Top-`ws_size` features by score, generalized support always included."""
    pri = jnp.where(gsupp_mask, jnp.inf, scores)
    _, ws = jax.lax.top_k(pri, ws_size)
    return ws


# ------------------------------------------------------- sharded working sets
# Per-shard primitives of the mesh-native engine (DESIGN.md §6). All of them
# run INSIDE shard_map: arrays are the local feature block [width], indices in
# the returned working set are GLOBAL (block-sharded layout: global index =
# shard * width + local index). `model_axis=None` means the features are NOT
# split (a size-1 model axis): every collective and ownership mask is elided
# statically, so the lowered program is the exact single-device one — the 1x1
# mesh is bit-identical to the dense engine by construction, and a (k, 1)
# data-parallel mesh pays zero feature-axis collectives.

def select_working_set_local(scores_loc, gsupp_loc, ws_size: int, model_axis):
    """Exact distributed top-k selection, support always retained.

    Local top-k per feature shard, all_gather of the (value, global-index)
    candidates over `model_axis`, global top-k over the union. Per shard we
    keep min(ws_size, width) candidates — a smaller local k (the historical
    `p // n_shards` cap) can drop generalized-support coordinates when the
    support concentrates on one shard. With this choice the union always
    holds >= ws_size candidates and every support coordinate (priority +inf,
    |gsupp| <= ws_size by the bucket policy) survives both top-k rounds.
    """
    if model_axis is None:
        return select_working_set(scores_loc, gsupp_loc, ws_size)
    pri = jnp.where(gsupp_loc, jnp.inf, scores_loc)
    width = pri.shape[0]
    loc_k = min(ws_size, width)
    v, i = jax.lax.top_k(pri, loc_k)
    gi = i + jax.lax.axis_index(model_axis) * width
    v_all = collectives.all_gather(v, model_axis).reshape(-1)
    i_all = collectives.all_gather(gi, model_axis).reshape(-1)
    _, sel = jax.lax.top_k(v_all, ws_size)
    return i_all[sel]


def shard_ws_mask(ws, width: int, model_axis):
    """(owned-mask, local index) of a global working set on this shard.

    mask is None when the features are unsplit (everything is owned)."""
    if model_axis is None:
        return None, ws
    mine = (ws // width) == jax.lax.axis_index(model_axis)
    return mine, jnp.where(mine, ws % width, 0)


def gather_ws_vec(vec_loc, mine, loc_idx, model_axis):
    """vec[ws] replicated over the model axis (masked gather + psum).

    Works on scalar coordinates (vec [width] -> [K]) and multitask blocks
    (vec [width, T] -> [K, T]): the ownership mask broadcasts over the
    trailing task dimension.
    """
    if mine is None:
        return vec_loc[loc_idx]
    rows = vec_loc[loc_idx]
    mask = mine if rows.ndim == 1 else mine[:, None]
    return collectives.psum(jnp.where(mask, rows, 0), model_axis)


def gather_ws_cols(X_loc, mine, loc_idx, model_axis):
    """X[:, ws] -> [n_loc, K]: data-sharded rows, replicated over model.

    Split features: each shard copies the K columns one at a time into a
    [K, n_loc] buffer (an unowned one reads column 0 and is masked), then
    psums it. A v5e compile of the one-op gather instead copies row slices
    of the whole shard first at n_loc = 50,000: about the shard again in
    temporaries (6 GB of a 16 GB chip at p_loc = 30,000) and in HBM
    traffic, every step."""
    if mine is None:
        return X_loc[:, loc_idx]

    def copy(k, cols):
        col = jax.lax.dynamic_slice_in_dim(X_loc, loc_idx[k], 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(cols, col.T, k, axis=0)

    K = loc_idx.shape[0]
    cols = jax.lax.fori_loop(0, K, copy,
                             jnp.zeros((K, X_loc.shape[0]), X_loc.dtype))
    return collectives.psum(cols * mine.astype(X_loc.dtype)[:, None],
                            model_axis).T


def scatter_ws(vec_loc, mine, loc_idx, vals):
    """vec[ws] = vals on the owning shard (out-of-range rows dropped)."""
    if mine is None:
        return vec_loc.at[loc_idx].set(vals)
    idx = jnp.where(mine, loc_idx, vec_loc.shape[0])
    return vec_loc.at[idx].set(vals, mode="drop")


def ws_occupancy(beta_ws):
    """Fraction of the gathered working-set slots holding a nonzero (block)
    coefficient after the inner solve — the bucket-utilization diagnostic
    the telemetry ring records per outer iteration (repro.obs, DESIGN.md
    §11.1): occupancy near 1.0 means the bucket is saturated and escalation
    is imminent; near 0.0 the bucket over-provisions. Multitask blocks
    ``[K, T]`` count a slot occupied when any task coefficient is nonzero.
    Traced; mesh-safe without collectives (beta_ws is replicated)."""
    nz = jnp.any(beta_ws != 0, axis=-1) if beta_ws.ndim == 2 \
        else (beta_ws != 0)
    return jnp.mean(nz.astype(beta_ws.dtype if beta_ws.dtype.kind == "f"
                              else jnp.float64))


def candidate_columns(cand_idx, cand_cols, ws, p: int):
    """Recover ``X[:, ws]`` ([n, K]) from the fused kernel's candidate buffer.

    The host-free merge for the fused score→select→gather kernel: cand_idx
    [C] int32 (global feature indices, entries >= p are exhausted-tile
    padding) and cand_cols [C, n] (the matching columns) come out of the
    kernel; ``ws`` is the final working set from ``select_working_set`` on
    the kernel-emitted scores. Every ws entry is guaranteed to appear in
    cand_idx (each tile emits its own top-``kc`` under the same total order
    as ``lax.top_k``, and kc >= the tile's share of any global top-K), so an
    inverse index built with a dropped scatter maps ws rows to candidate
    rows without touching X again. Duplicate cand_idx entries (exhausted
    tiles re-emitting already-picked rows) are harmless: every duplicate
    carries the same exact column copy.
    """
    C = cand_idx.shape[0]
    pos = jnp.zeros((p,), jnp.int32).at[cand_idx].set(
        jnp.arange(C, dtype=jnp.int32), mode="drop")
    return cand_cols[pos[ws]].T
