"""What decides `correct`: the timed path's answers against the reference.

Every number here is a gap between what the program returned and what
the plain reference (reference.py) says, and is held to a limit that the
configuration file states under "limits":

  recall_at_10   share of the reference's exact 10 nearest neighbours
                 among the ids returned (at least the limit: the recall
                 floor the configuration states);
  dist_err       largest relative error of a returned distance against
                 the reference's distance for the same (query, id);
  graph_dist_err the same for the stored distances of the built graph's
                 edges, over a sample of vertices drawn from the seed;
  bad_ids        rows holding an id out of range, a repeated id, or (for
                 a query) fewer than k ids; graph rows holding a
                 self-edge, a repeat or an id out of range (limit 0);
  unanswered     requests due in the window that got no answer (limit 0).
"""
from __future__ import annotations

import numpy as np

from bench import reference as R

# relative errors are taken against max(d, FLOOR * median d): a distance
# near 0 would make any rounding look large
FLOOR = 1e-3


def _rel_err(got, want) -> float:
    if len(want) == 0:
        return 0.0
    den = np.maximum(want, FLOOR * max(float(np.median(want)), 1e-30))
    err = np.abs(got.astype(np.float64) - want) / den
    return float(np.max(np.where(np.isfinite(err), err, np.inf)))


def recall(found, true) -> float:
    """Mean share of each row of `true` found in the same row of `found`."""
    hits = sum(len(set(f[f >= 0].tolist()) & set(t.tolist()))
               for f, t in zip(np.asarray(found), np.asarray(true)))
    return hits / np.asarray(true).size


def bad_rows(ids, n: int, k: int | None = None, owners=None) -> int:
    """Rows with an id >= n or < -1, a repeated id, a self-edge (owners
    given) or, with k, fewer than k ids."""
    ids = np.asarray(ids)
    live = ids >= 0
    bad = np.any((ids >= n) | (ids < -1), axis=1)
    srt = np.sort(np.where(live, ids, -1 - np.arange(ids.shape[1])), axis=1)
    bad |= np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    if owners is not None:
        bad |= np.any(ids == np.asarray(owners)[:, None], axis=1)
    if k is not None:
        bad |= live.sum(axis=1) < k
    return int(bad.sum())


def answers(x, queries, ids, dists, k: int, true_ids=None) -> dict:
    """Numbers for answered queries: ids/dists (Q, k) as returned."""
    ids, dists = np.asarray(ids), np.asarray(dists, np.float32)
    queries = np.asarray(queries, np.float32)
    n = x.shape[0]
    if true_ids is None:
        true_ids = R.knn(x, queries, k)[0]
    ok = (ids >= 0) & (ids < n)
    q_rows, slots = np.nonzero(ok)
    want = R.pair_sqdist(x, queries[q_rows], ids[q_rows, slots])
    return {"recall_at_10": recall(ids, true_ids),
            "dist_err": _rel_err(dists[q_rows, slots], want),
            "bad_ids": bad_rows(ids, n, k)}


def graph(x, pool_ids, pool_dists, rows) -> dict:
    """Numbers for a built graph, over the vertices `rows`."""
    rows = np.asarray(rows)
    ids = np.asarray(pool_ids)[rows]
    dists = np.asarray(pool_dists, np.float32)[rows]
    n = x.shape[0]
    r_i, slots = np.nonzero((ids >= 0) & (ids < n))
    want = R.pair_sqdist(x, rows[r_i], ids[r_i, slots])
    return {"graph_dist_err": _rel_err(dists[r_i, slots], want),
            "graph_bad_ids": bad_rows(ids, n, owners=rows)}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}).  recall_at_10 must reach
    its limit; every other number must not pass its own."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        good = value >= limit if name == "recall_at_10" else value <= limit
        ok &= bool(good)
        out[name] = {"value": value, "limit": limit}
    return ok, out
