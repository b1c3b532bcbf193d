"""Cluster/color row packing for multicolor Gauss-Seidel (paper Alg. 4).

The apply-phase layout is a per-color padded int32 matrix
``rows[c][n_clusters_c, max_len_c]`` (sentinel = V, scatter-dropped).

Host backend: the numpy packing moved from
``solvers.multicolor_gs._pack_clusters``.  Device backend: one stable
device sort by ``(color(cluster), cluster, vertex)`` plus a scatter into
a single ``[num_clusters, max_len]`` block; the per-color views are
slices of that device-resident block, elementwise identical to the host
arrays (asserted in ``tests/test_multilevel.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops


# ---------------------------------------------------------------------------
# host backend (numpy; the reference)
# ---------------------------------------------------------------------------

def pack_clusters_host(labels: np.ndarray, cluster_colors: np.ndarray,
                       num_colors: int, v: int):
    """Group rows by (color(cluster), cluster) into padded per-color arrays."""
    order = np.lexsort((np.arange(v), labels))
    sorted_labels = labels[order]
    # row lists per cluster (ascending vertex ids — deterministic)
    starts = np.flatnonzero(np.r_[True, sorted_labels[1:] != sorted_labels[:-1]])
    ends = np.r_[starts[1:], v]
    cluster_ids = sorted_labels[starts]
    color_rows = []
    for c in range(num_colors):
        sel = np.flatnonzero(cluster_colors[cluster_ids] == c)
        if len(sel) == 0:
            continue
        lens = ends[sel] - starts[sel]
        max_len = int(lens.max())
        mat = np.full((len(sel), max_len), v, dtype=np.int32)
        for i, s in enumerate(sel):
            mat[i, : lens[i]] = order[starts[s]:ends[s]]
        color_rows.append(jnp.asarray(mat))
    return tuple(color_rows)


# ---------------------------------------------------------------------------
# device backend
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_colors",))
def _pack_analyze_device(labels, cluster_colors, *, num_colors: int):
    """Device sort + per-(color, cluster) geometry.

    Returns ``(row_order[V], per_color_clusters[C], per_color_maxlen[C],
    max_len)`` where ``row_order`` lists vertices sorted by (cluster
    color, cluster id, vertex id).
    """
    v = labels.shape[0]
    c = max(1, num_colors)
    color_of_v = cluster_colors[labels].astype(jnp.int64)
    key = (color_of_v * v + labels.astype(jnp.int64))
    row_order = jnp.argsort(key, stable=True).astype(jnp.int32)
    lab_s = labels[row_order]
    sizes = jnp.zeros(v, jnp.int32).at[labels].add(1)
    # one representative row per cluster -> per-color cluster counts/maxlens
    head = jnp.concatenate([jnp.ones(1, bool), lab_s[1:] != lab_s[:-1]])
    ccol = jnp.clip(cluster_colors[lab_s], 0, c)
    csize = sizes[lab_s]
    nclusters = jnp.zeros(c + 1, jnp.int32).at[
        jnp.where(head, ccol, c)].add(1)[:-1]
    maxlen = jnp.zeros(c + 1, jnp.int32).at[
        jnp.where(head, ccol, c)].max(csize)[:-1]
    return row_order, nclusters, maxlen, jnp.max(sizes)


@functools.partial(jax.jit, static_argnames=("num_clusters", "max_len"))
def _pack_rows_device(row_order, labels, *, num_clusters: int, max_len: int):
    """Scatter the sorted vertices into one padded ``[num_clusters,
    max_len]`` block, cluster rows ordered by (color, cluster id) — the
    concatenation of the per-color host matrices (sentinel = V)."""
    v = labels.shape[0]
    lab_s = labels[row_order]
    head = jnp.concatenate([jnp.ones(1, bool), lab_s[1:] != lab_s[:-1]])
    crow = xla_ops.cumsum(head.astype(jnp.int32)) - 1       # cluster rank
    pos = jnp.arange(v, dtype=jnp.int32)
    starts = jnp.where(head, pos, 0)
    starts = jax.lax.cummax(starts)                     # start of own cluster
    slot = pos - starts
    block = jnp.full((num_clusters, max(1, max_len)), v, jnp.int32)
    return block.at[crow, jnp.clip(slot, 0, max(1, max_len) - 1)].set(
        row_order, mode="drop")


def pack_clusters_device(labels, cluster_colors, num_colors: int, v: int):
    """Device packing; returns the same per-color tuple as the host
    backend, as slices of one device-resident block (no host copy of the
    packed rows — only the per-color geometry scalars come back)."""
    labels_j = jnp.asarray(np.asarray(labels, dtype=np.int32))
    colors_j = jnp.asarray(np.asarray(cluster_colors, dtype=np.int32))
    with jax.enable_x64(True):          # int64 (color, cluster) sort keys
        row_order, nclusters, maxlen, _ = _pack_analyze_device(
            labels_j, colors_j, num_colors=num_colors)
        ncl = np.asarray(nclusters[:num_colors])        # [C] ints (geometry)
        mll = np.asarray(maxlen[:num_colors])
        total = int(ncl.sum())
        lmax = int(mll.max()) if num_colors else 1
        block = _pack_rows_device(row_order, labels_j,
                                  num_clusters=max(1, total),
                                  max_len=max(1, lmax))
    color_rows = []
    start = 0
    for c in range(num_colors):
        n = int(ncl[c])
        if n == 0:
            continue
        color_rows.append(block[start:start + n, : int(mll[c])])
        start += n
    return tuple(color_rows)
