"""The plain reference and the comparison that decides ``correct``.

NumPy float32 over the vectors the arena stores; imports nothing of the
program and is handed nothing the program made. Semantics of both
deployments: exact top-k by cosine inside the caller's tenant, over live rows
only, the query normalised in f32 and rounded to the arena's dtype (the scan
is a product of two arrays of that dtype accumulated in f32) — or kept in
f32 where XLA's excess precision does so: see ``Comparison.answer``.

``int8_answers`` is the control: the same reference computed one precision
below the configuration's (bf16 -> int8 codes, per-row scale, exact integer
accumulation), put in the program's place. It has to come out NOT correct.

Reads that write (``chat`` retrievals, a mix's ``boost_share``): a boosting
request adds 1 to ``access_count`` and ``access_salience_boost`` to the
salience of its ``retrieval_cap`` best served rows, and
``neighbor_salience_boost`` to the salience of every graph neighbour of those
rows that is not itself among them (once a request, however many of its rows
touch it); salience is capped at 1.0 and a touched row takes the time of its
dispatch as ``last_accessed``. Increments are positive and the cap monotone,
so the state a window leaves depends on how often each row was boosted and
not on the order: ``boost_bounds`` counts that from the reference's own exact
top-k of every completed request, and ``Comparison.state`` holds the rows the
program reads back to those counts (``state_errors``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

DTYPES = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32}


def unit(v: np.ndarray) -> np.ndarray:
    v = np.asarray(v, np.float32)
    return v / np.maximum(np.linalg.norm(v, axis=-1, keepdims=True), 1e-9)


def query_variants(rows, live, queries, k: int, dtype: str) -> list:
    """``topk_exact`` with the queries rounded to the arena's dtype, and
    with them kept in f32."""
    return [topk_exact(rows, live, stored(queries, dtype), k),
            topk_exact(rows, live, unit(queries), k)]


def stored(v: np.ndarray, dtype: str) -> np.ndarray:
    """What the arena holds for ``v`` and scores a query with: normalised in
    f32, rounded to the arena dtype, widened back to f32."""
    v = np.asarray(v, np.float32)
    v = v / np.maximum(np.linalg.norm(v, axis=-1, keepdims=True), 1e-9)
    return v.astype(DTYPES[dtype]).astype(np.float32)


def topk_exact(rows: np.ndarray, live: np.ndarray, queries: np.ndarray,
               k: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(scores [m, k], idx [m, k], all_scores [m, n]) — masked f32 cosine of
    every query against one tenant's rows, best first, ties by row."""
    all_scores = queries.astype(np.float32) @ rows.astype(np.float32).T
    masked = np.where(live[None, :], all_scores, -np.inf)
    order = np.argsort(-masked, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(masked, order, axis=1), order, all_scores


def _int8_codes(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    scale = np.maximum(np.abs(x).max(axis=-1, keepdims=True), 1e-12) / 127.0
    return np.clip(np.rint(x / scale), -127, 127).astype(np.int32), scale


def int8_answers(rows: np.ndarray, live: np.ndarray, queries: np.ndarray,
                 k: int) -> List[Tuple[List[int], List[float]]]:
    """The control's answers: top-k of int8 rows against int8 queries."""
    rq, rs = _int8_codes(rows)
    qq, qs = _int8_codes(queries)
    scores = (qq @ rq.T).astype(np.float32) * qs * rs.T
    masked = np.where(live[None, :], scores, -np.inf)
    order = np.argsort(-masked, axis=1, kind="stable")[:, :k]
    out = []
    for i in range(order.shape[0]):
        keep = [int(j) for j in order[i] if np.isfinite(masked[i, j])]
        out.append((keep, [float(masked[i, j]) for j in keep]))
    return out


def boost_bounds(rows: np.ndarray, live: np.ndarray, queries: np.ndarray,
                 times: np.ndarray, k: int, dtype: str, tol: float,
                 neighbours: Optional[List[np.ndarray]] = None,
                 max_neighbours: int = 0, block: int = 2048
                 ) -> Dict[str, np.ndarray]:
    """How often each of one tenant's rows was boosted by ``queries``
    (boosting requests of that tenant, request i sent ``times[i]`` times, each
    taking its ``k`` best rows): ``acc_lo``/``acc_hi`` [n] the fewest and the
    most access boosts a row can have, ``nbr_lo``/``nbr_hi`` the same for
    neighbour boosts, ``requests`` the boosting requests and ``taken`` the
    rows each takes. The two differ only for a row within ``tol`` of the
    boundary of a request's top-k, at the query rounded to the arena's dtype
    or kept in f32 (``query_variants``): there the program may rightly serve
    its neighbour in rank. ``neighbours[j]`` are the rows joined to row j by
    an edge, in either direction; a boost reaches the first
    ``max_neighbours`` of them, and which come first is the program's own
    order, so a list longer than that is refused: the configuration's graph
    has to stay under it."""
    n = rows.shape[0]
    out = {name: np.zeros(n, np.int64)
           for name in ("acc_lo", "acc_hi", "nbr_lo", "nbr_hi")}
    take = min(int(k), int(live.sum()))
    out["requests"], out["taken"] = int(np.sum(times)), take
    if not take or not len(queries):
        return out
    adj = None
    if neighbours is not None:
        longest = max((len(ns) for ns in neighbours), default=0)
        if longest > max_neighbours:
            raise ValueError(f"a row has {longest} neighbours, a boost "
                             f"reaches {max_neighbours}")
        adj = np.zeros((n, n), bool)
        for j, ns in enumerate(neighbours):
            adj[j, np.asarray(ns, np.int64)] = True
        adj |= adj.T
        np.fill_diagonal(adj, False)
    rows32 = rows.astype(np.float32)
    for a in range(0, len(queries), block):
        q, m = queries[a:a + block], np.asarray(times[a:a + block], np.int64)
        sure = np.ones((len(q), n), bool)
        may = np.zeros((len(q), n), bool)
        for qv in (stored(q, dtype), unit(q)):
            s = np.where(live[None, :], qv @ rows32.T, -np.inf)
            best = -np.sort(-s, axis=1)
            kth = best[:, take - 1:take]
            nxt = best[:, take:take + 1] if n > take else np.full_like(kth, -np.inf)
            sure &= s > nxt + tol
            may |= s >= kth - tol
        out["acc_lo"] += m @ sure
        out["acc_hi"] += m @ may
        if adj is not None:
            # a neighbour of a taken row, not itself taken
            near_sure = (sure.astype(np.int32) @ adj > 0) & ~may & live[None, :]
            near_may = (may.astype(np.int32) @ adj > 0) & ~sure & live[None, :]
            out["nbr_lo"] += m @ near_sure
            out["nbr_hi"] += m @ near_may
    return out


class Comparison:
    """The numbers ``correct`` rests on, each held to a limit of its own.

    score_gap      widest |served score - reference score of that row|
    rank_errors    served hits whose reference score is not, within the
                   score limit, what the reference's hit of that rank scores
                   (a wrong row, a row of a dead or duplicate fact)
    foreign_ids    served ids that are not a fact of the caller's tenant
    count_errors   answers with another number of hits than the reference
    unanswered     requests of the sample that returned no answer at all
    swallowed      failures the program retried or swallowed during the
                   window (its reliability counters), set by the harness
    state_errors   rows whose access count, salience or last access is not
                   what the boosting requests of the window leave, and
                   tenants whose boosts do not add up (``state``). Compared
                   only where the limits name it: under a mix that boosts.
    """

    NAMES = ("score_gap", "rank_errors", "foreign_ids", "count_errors",
             "unanswered", "swallowed")
    OPTIONAL = ("state_errors",)

    def __init__(self, limits: Dict[str, float]):
        missing = [n for n in self.NAMES if n not in limits]
        if missing:
            raise ValueError(f"limits lack {missing}")
        self.names = self.NAMES + tuple(n for n in self.OPTIONAL if n in limits)
        self.limits = {n: float(limits[n]) for n in self.names}
        self.score_gap = 0.0
        self.rank_errors = self.foreign_ids = 0
        self.count_errors = self.unanswered = self.swallowed = 0
        self.state_errors = 0
        self.answers = 0
        self.first_fault: Optional[str] = None

    def _fault(self, msg: str) -> None:
        if self.first_fault is None:
            self.first_fault = msg

    def unanswered_request(self, label: str) -> None:
        self.unanswered += 1
        self._fault(f"{label}: no answer")

    def foreign(self, label: str, what: str) -> None:
        self.foreign_ids += 1
        self._fault(f"{label}: served {what}")

    def answer(self, label: str, got_idx: Sequence[int],
               got_scores: Optional[Sequence[float]], variants, live: np.ndarray
               ) -> None:
        """One served answer (fact indices inside the tenant, best first)
        against the reference: ``variants`` is a list of one row each of
        ``topk_exact``'s outputs ``(ref_s, ref_i, all_s)``. An answer has
        to agree with ONE of them as a whole; the widest score gap and the
        rank errors of the variant it agrees with best are what count.

        Two variants exist where the configuration leaves a choice open:
        the program rounds the query to the arena's dtype before the scan,
        and XLA may keep it in f32 (``xla_allow_excess_precision``; which,
        depends on the compiled shape). Both are the same cosine to the
        arena's precision, and a whole dispatch takes one or the other."""
        self.answers += 1
        tol = self.limits["score_gap"]
        n_ref = int(np.isfinite(variants[0][0]).sum())
        if len(got_idx) != n_ref:
            self.count_errors += 1
            self._fault(f"{label}: {len(got_idx)} hits, reference has {n_ref}")
        best = None
        for ref_s, ref_i, all_s in variants:
            gap, errs, fault = 0.0, 0, None
            for r, j in enumerate(got_idx[:n_ref]):
                if not (0 <= j < live.shape[0]) or not live[j]:
                    errs += 1
                    fault = fault or f"{label}: hit {j} is not a live fact"
                    continue
                if got_scores is not None:
                    g = abs(float(got_scores[r]) - float(all_s[j]))
                    if g > gap:
                        gap = g
                        if g > tol:
                            fault = fault or (
                                f"{label}: rank {r} fact {j} scored "
                                f"{got_scores[r]:.7f}, reference "
                                f"{all_s[j]:.7f}")
                if abs(float(all_s[j]) - float(ref_s[r])) > tol:
                    errs += 1
                    fault = fault or (
                        f"{label}: rank {r} is fact {j} (reference score "
                        f"{all_s[j]:.6f}); the reference's rank {r} is fact "
                        f"{int(ref_i[r])} ({ref_s[r]:.6f})")
            if best is None or (errs, gap) < best[:2]:
                best = (errs, gap, fault)
        errs, gap, fault = best
        self.rank_errors += errs
        self.score_gap = max(self.score_gap, gap)
        if fault:
            self._fault(fault)

    def state(self, label: str, got: Dict[str, np.ndarray],
              want: Dict[str, np.ndarray], boost: Dict[str, float],
              window: Tuple[float, float]) -> None:
        """One tenant's rows as the program holds them after the window
        (``got``: ``access_count``, ``salience``, ``last_accessed``, a row
        each) against ``boost_bounds``' counts (``want``). ``boost`` gives
        the installed salience (``salience0``) and the two increments;
        ``window`` the index's clock when the window opened and when the
        state was read. A row counts once, whatever is wrong with it; a
        tenant whose access counts do not add up to its boosting requests
        times the rows each takes counts once more."""
        count = np.asarray(got["access_count"], np.int64)
        sal = np.asarray(got["salience"], np.float64)
        seen = np.asarray(got["last_accessed"], np.float64)
        eps, slack = 1e-5, 0.01

        def salience(acc, nbr):
            return np.minimum(1.0, boost["salience0"]
                              + acc * boost["access_salience_boost"]
                              + nbr * boost["neighbor_salience_boost"])

        bad_count = (count < want["acc_lo"]) | (count > want["acc_hi"])
        acc = np.clip(count, want["acc_lo"], want["acc_hi"])
        bad_sal = ((sal < salience(acc, want["nbr_lo"]) - eps)
                   | (sal > salience(acc, want["nbr_hi"]) + eps))
        inside = (seen >= window[0] - slack) & (seen <= window[1] + slack)
        touched = (count > 0) | (want["acc_lo"] + want["nbr_lo"] > 0)
        never = want["acc_hi"] + want["nbr_hi"] == 0
        bad_seen = np.where(touched, ~inside,
                            (seen != 0.0) & (never | ~inside))
        bad = bad_count | bad_sal | bad_seen
        self.state_errors += int(bad.sum())
        if bad.any():
            j = int(np.argmax(bad))
            self._fault(
                f"{label}: row {j} holds access_count {count[j]} salience "
                f"{sal[j]:.6f} last_accessed {seen[j]:.3f}; the replay "
                f"{want['acc_lo'][j]}-{want['acc_hi'][j]} access boosts, "
                f"{want['nbr_lo'][j]}-{want['nbr_hi'][j]} neighbour boosts, "
                f"window {window[0]:.3f}-{window[1]:.3f}")
        total = want["requests"] * want["taken"]
        if int(count.sum()) != total:
            self.state_errors += 1
            self._fault(f"{label}: access counts add up to {int(count.sum())}"
                        f", its {want['requests']} boosting requests x "
                        f"{want['taken']} rows to {total}")

    def numbers(self) -> Dict[str, Dict[str, float]]:
        return {n: {"value": float(getattr(self, n)), "limit": self.limits[n]}
                for n in self.names}

    @property
    def correct(self) -> bool:
        return self.answers > 0 and all(
            v["value"] <= v["limit"] for v in self.numbers().values())
