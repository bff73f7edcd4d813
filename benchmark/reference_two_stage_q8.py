"""The plain reference of the int8 deployment (``lme5m-int8``) and the
comparison that decides ``correct`` there.

NumPy float32 over the vectors the arena stores; imports nothing of the
program and is handed nothing the program made. Semantics of the deployment,
per tenant, over live rows only:

1. COARSE. Every stored (bf16) row and the unit f32 query become int8 codes
   by the documented rule — per-row symmetric, ``scale = amax / 127``,
   ``code = round-to-nearest(x / scale)`` clipped to +-127. The coarse score
   is the INTEGER dot of the codes times the query's scale times the row's
   scale, in f32. The ``coarse_fetch`` best rows by that score survive, ties
   to the lower row. ``coarse_fetch`` is read from the configuration's file
   (``benchmark/configs/lme5m-int8.json``), not restated here.
2. RESCORE. The survivors' scores are computed again from the stored rows in
   f32 (the query rounded to the arena's dtype, or kept in f32 where XLA's
   excess precision does so: ``Comparison.answer``), and the top-k of THOSE
   is the answer: ids, ranks and scores are the exact ones of the rows the
   coarse stage let through.

The interface is ``benchmark/reference.py``'s (``unit``, ``stored``,
``query_variants``, ``int8_answers``, ``Comparison``); the comparison itself
is that file's, so a number means the same in every cell. ``int8_answers`` is
the control — the coarse stage's answers served as they are, its int8 scores
with them: one precision below what the deployment promises. It has to come
out NOT correct.
"""

from __future__ import annotations

import json
import os
from typing import List, Tuple

import numpy as np

from benchmark.reference import (DTYPES, Comparison, stored,  # noqa: F401
                                 unit)

_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs",
                       "lme5m-int8.json")


def coarse_fetch() -> int:
    """Rows the coarse stage lets through, as the configuration states it."""
    with open(_CONFIG) as f:
        return int(json.load(f)["coarse_fetch"])


def int8_codes(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(codes [n, d] as f64 integers in +-127, scale [n, 1] f32); a zero row
    has scale 0 and codes 0."""
    x = np.asarray(x, np.float32)
    amax = np.abs(x).max(axis=-1, keepdims=True)
    scale = np.where(amax > 0, amax / np.float32(127.0), 0.0).astype(np.float32)
    safe = np.where(scale > 0, scale, np.float32(1.0))
    codes = np.clip(np.rint(x / safe), -127, 127)
    return codes.astype(np.float64), scale


def coarse_scores(rows: np.ndarray, live: np.ndarray, q_unit: np.ndarray
                  ) -> np.ndarray:
    """[m, n] f32: integer dot x query scale x row scale; dead rows -inf.
    The dot of two codes is an integer below 2**24: exact in any float."""
    rq, rs = int8_codes(rows)
    qq, qs = int8_codes(q_unit)
    dots = (qq @ rq.T).astype(np.float32)
    scores = dots * qs * rs.T
    return np.where(live[None, :], scores, -np.inf).astype(np.float32)


def topk_two_stage(rows: np.ndarray, live: np.ndarray, q_unit: np.ndarray,
                   q_score: np.ndarray, k: int, fetch: int
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(scores [m, k], idx [m, k], all_scores [m, n]) as
    ``reference.topk_exact`` gives them, of the two-stage answer: the
    ``fetch`` best by coarse score, then the top-k of their f32 scores
    against ``q_score`` (best first, ties to the better coarse rank)."""
    coarse = coarse_scores(rows, live, q_unit)
    keep = np.argsort(-coarse, axis=1, kind="stable")[:, :fetch]
    all_scores = q_score.astype(np.float32) @ rows.astype(np.float32).T
    cand = np.where(np.isfinite(np.take_along_axis(coarse, keep, axis=1)),
                    np.take_along_axis(all_scores, keep, axis=1), -np.inf)
    order = np.argsort(-cand, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(cand, order, axis=1),
            np.take_along_axis(keep, order, axis=1), all_scores)


def query_variants(rows, live, queries, k: int, dtype: str) -> list:
    """The two-stage answer with the rescore's query rounded to the arena's
    dtype, and with it kept in f32; the coarse stage sees the unit f32 query
    either way."""
    q_unit = unit(queries)
    fetch = coarse_fetch()
    return [topk_two_stage(rows, live, q_unit, stored(queries, dtype), k, fetch),
            topk_two_stage(rows, live, q_unit, q_unit, k, fetch)]


def int8_answers(rows: np.ndarray, live: np.ndarray, queries: np.ndarray,
                 k: int) -> List[Tuple[List[int], List[float]]]:
    """The control's answers: the coarse stage's top-k with its int8 scores,
    no rescore."""
    coarse = coarse_scores(rows, live, unit(queries))
    order = np.argsort(-coarse, axis=1, kind="stable")[:, :k]
    out = []
    for i in range(order.shape[0]):
        keep = [int(j) for j in order[i] if np.isfinite(coarse[i, j])]
        out.append((keep, [float(coarse[i, j]) for j in keep]))
    return out
