"""The comparison that decides ``correct``.

Every request due in the measured window is judged by what it answered,
against the reference's exact answer for its query:

- ``unanswered``: requests that were refused, failed or never answered
  (a late answer is late, not wrong).  Limit 0.
- ``malformed``: answers without exactly ``k`` distinct in-range ids in
  ascending order of distance.  Limit 0.
- ``dist_gap``: the widest relative gap between a returned distance and
  the exact squared L2 of the id it names.  The configuration guarantees
  exact distances from the re-rank against the raw vectors; the limit sits
  between float32 rounding and what a bfloat16 scorer reads (``PERF.md``
  gives the readings).
- ``recall_miss``: one minus Recall@10 over the window's answers, against
  the configuration's stated recall floor.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from reference import exact_sq_l2


def compare(corpus: np.ndarray, queries: np.ndarray,
            answers: Sequence[Optional[tuple]], exact_ids: np.ndarray,
            k: int, limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """``answers[i]`` is (ids, dists) for ``queries[i]``, or None where no
    answer came; ``exact_ids`` (Q, k) is the reference's.  Returns each
    number beside its limit."""
    n = len(corpus)
    unanswered = malformed = 0
    gaps: List[float] = [0.0]
    hits = judged = 0
    for qi, ans in enumerate(answers):
        if ans is None:
            unanswered += 1
            continue
        ids = np.asarray(ans[0]).astype(np.int64).ravel()
        dists = np.asarray(ans[1]).astype(np.float64).ravel()
        if malformed_reason(ids, dists, k, n):
            malformed += 1
            continue
        exact = exact_sq_l2(corpus, queries[qi:qi + 1], ids[None])[0]
        gaps.append(float(np.max(np.abs(dists - exact)
                                 / np.maximum(exact, 1e-30))))
        hits += len(np.intersect1d(ids, exact_ids[qi]))
        judged += 1
    recall = hits / (k * judged) if judged else 0.0
    values = {"unanswered": unanswered, "malformed": malformed,
              "dist_gap": max(gaps), "recall_miss": 1.0 - recall}
    return {name: {"value": values[name], "limit": limits[name]}
            for name in ("unanswered", "malformed", "dist_gap",
                         "recall_miss")}


def malformed_reason(ids: np.ndarray, dists: np.ndarray, k: int,
                     n: int) -> str:
    """Why an answer is malformed, or '' when it is well formed."""
    if len(ids) != k or len(dists) != k:
        return f"{len(ids)} ids and {len(dists)} distances, not {k}"
    if len(set(ids.tolist())) != k:
        return "repeated ids"
    if ids.min() < 0 or ids.max() >= n:
        return "ids out of range"
    if np.any(np.diff(dists) < 0) or not np.all(np.isfinite(dists)):
        return "distances not finite and ascending"
    return ""


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())

