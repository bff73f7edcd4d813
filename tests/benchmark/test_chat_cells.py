"""Reads that write as traffic (PR 38): a mix's ``boost_share``, the plan it
makes, the reference's boost arithmetic, and ``state_errors`` — sound runs
read 0, and every way of breaking what a boosting dispatch leaves behind
comes out NOT correct by that number alone. CPU debug runs at tiny sizes; no
number read here is a device number."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
import faults  # noqa: E402
from benchmark import corpus, families, harness, reference  # noqa: E402

GOLDEN = json.load(open(os.path.join(HERE, "data", "plan_golden.json")))
OTHERS = ("score_gap", "rank_errors", "foreign_ids", "count_errors",
          "unanswered", "swallowed")


def _digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def _plan(cell, seed, seconds=2.0, **override):
    _, cfg, mix = harness.cell_files(cell, ROOT, debug=True)
    mix.update(override)
    starts = corpus.tenant_starts(cfg["rows"], cfg["tenants"])
    seen = []

    def make(queries, tenants, k, boost):
        seen.append(boost)
        return [(int(t), k) for t in tenants]
    return harness.ServePlan(cfg, mix, seed, seconds, starts, 0, make), seen


# ---------------------------------------------------------------- the plan

@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_plan_of_a_mix_without_boost_share_is_the_parent_s_bit_for_bit(key):
    """The golden was written by the PARENT's ``ServePlan`` (commit 9beb578)
    for three accepted cells at their debug sizes: due times, tenants,
    facts, the kept set, and what ``make_requests`` was handed."""
    cell, _, seed = key.partition(":")
    want = GOLDEN[key]
    plan, seen = _plan(cell, int(seed))
    assert plan.boost is None and seen == [None]
    assert len(plan.tenant) == want["n"]
    if want["due"] is None:
        assert plan.due is None
    else:
        assert _digest(np.round(plan.due * 1e9).astype(np.int64)) == want["due"]
        assert [float(x) for x in plan.due[:4]] == want["due_head"]
    assert _digest(plan.tenant.astype(np.int64)) == want["tenant"]
    assert _digest(plan.fact.astype(np.int64)) == want["fact"]
    assert _digest(np.flatnonzero(plan.keep).astype(np.int64)) == want["keep"]
    assert int(plan.keep.sum()) == want["kept"]
    np.testing.assert_allclose(plan.queries[:3, :4].ravel(),
                               want["query_head"], rtol=0, atol=1e-6)
    assert _digest(np.asarray(plan.requests, np.int64)) == want["requests"]


@pytest.mark.parametrize("share,marked", [(1.0, 300), (0.5, 150), (0.0, 0),
                                          (0.3333, 100)])
def test_boost_share_marks_that_many_requests_and_moves_nothing_else(share, marked):
    plain, _ = _plan("share.serve", 7)
    plan, seen = _plan("share.serve", 7, boost_share=share)
    assert plan.boost.dtype == bool and int(plan.boost.sum()) == marked
    assert len(seen) == 1 and seen[0] is plan.boost
    for name in ("due", "tenant", "fact", "queries", "keep"):
        np.testing.assert_array_equal(getattr(plan, name), getattr(plain, name))
    again, _ = _plan("share.serve", 7, boost_share=share)
    np.testing.assert_array_equal(again.boost, plan.boost)
    other, _ = _plan("share.serve", 8, boost_share=share)
    assert int(other.boost.sum()) == marked       # the same work, another order
    assert plan.check_tenants == sorted(set(plan.tenant[plan.keep].tolist()))


@pytest.mark.parametrize("share", [-0.1, 1.5])
def test_boost_share_outside_0_1_is_refused(share):
    with pytest.raises(ValueError, match="no share"):
        _plan("share.serve", 7, boost_share=share)


def test_make_requests_builds_the_scheduler_s_own_boosting_requests():
    from benchmark import deploy
    q = np.eye(4, 8, dtype=np.float32)
    flags = np.array([True, False, True, False])
    reqs = deploy.make_requests(q, [3, 3, 4, 5], 5, flags)
    assert [r.boost for r in reqs] == [True, False, True, False]
    assert {type(r).__name__ for r in reqs} == {"RetrievalRequest"}
    assert [r.tenant for r in reqs] == ["t00003", "t00003", "t00004", "t00005"]
    assert not any(r.boost for r in deploy.make_requests(q, [3, 3, 4, 5], 5))


# ------------------------------------------- the reference's boost arithmetic

def _rows(n=12, dim=16, seed=0):
    return reference.stored(np.random.default_rng(seed).standard_normal((n, dim)),
                            "bfloat16")


BOOST = {"salience0": 0.6, "access_salience_boost": 0.05,
         "neighbor_salience_boost": 0.02, "retrieval_cap": 5, "serve_max_nbr": 32}


def _state(n, acc, nbr=None, when=50.0):
    acc = np.asarray(acc, np.int64)
    nbr = np.zeros(n, np.int64) if nbr is None else np.asarray(nbr, np.int64)
    touched = (acc + nbr) > 0
    return {"access_count": acc,
            "salience": np.minimum(1.0, 0.6 + 0.05 * acc + 0.02 * nbr
                                   ).astype(np.float32),
            "last_accessed": np.where(touched, when, 0.0).astype(np.float32)}


def test_boost_bounds_counts_each_request_s_top_rows_times_its_sends():
    rows = _rows()
    live = np.ones(12, bool)
    q = rows[[2, 7, 2]] + 0.01
    want = reference.boost_bounds(rows, live, q, np.array([1, 3, 2]), 5,
                                  "bfloat16", 1e-6)
    np.testing.assert_array_equal(want["acc_lo"], want["acc_hi"])
    _, order, _ = reference.topk_exact(rows, live, reference.stored(q, "bfloat16"), 5)
    by_hand = np.zeros(12, np.int64)
    for times, top in zip((1, 3, 2), order):
        by_hand[top] += times
    np.testing.assert_array_equal(want["acc_lo"], by_hand)
    assert want["requests"] == 6 and want["taken"] == 5
    assert want["acc_lo"].sum() == 30 and not want["nbr_hi"].any()


def test_boost_bounds_leaves_a_near_tie_at_the_boundary_open():
    rows = _rows()
    rows[5] = rows[4]                       # two rows score alike, always
    live = np.ones(12, bool)
    q = rows[[4]]
    s = (reference.stored(q, "bfloat16") @ rows.T)[0]
    k = int((s > s[4]).sum()) + 1           # the boundary falls between them
    want = reference.boost_bounds(rows, live, q, np.array([1]), k, "bfloat16", 1e-4)
    assert (want["acc_lo"][[4, 5]] == 0).all() and (want["acc_hi"][[4, 5]] == 1).all()
    assert want["acc_lo"].sum() == k - 1


def test_boost_bounds_boosts_each_neighbour_once_and_no_taken_row():
    rows = _rows()
    live = np.ones(12, bool)
    q = rows[[3]]
    _, order, _ = reference.topk_exact(rows, live, reference.stored(q, "bfloat16"), 2)
    a, b = (int(x) for x in order[0])
    rest = [j for j in range(12) if j not in (a, b)]
    c, d = rest[0], rest[1]
    # a-b (both taken), a-c and b-c (c shared: once), d-a given the other way
    lists = corpus.neighbour_lists(12, [(a, b, .5), (a, c, .5), (b, c, .5), (d, a, .5)])
    want = reference.boost_bounds(rows, live, q, np.array([4]), 2, "bfloat16",
                                  1e-6, lists, 32)
    by_hand = np.zeros(12, np.int64)
    by_hand[[c, d]] = 4
    np.testing.assert_array_equal(want["nbr_lo"], by_hand)
    np.testing.assert_array_equal(want["nbr_hi"], by_hand)
    with pytest.raises(ValueError, match="a boost reaches 2"):
        reference.boost_bounds(rows, live, q, np.array([1]), 2, "bfloat16",
                               1e-6, lists, 2)


def _compare(got, want, window=(40.0, 90.0)):
    cmp = reference.Comparison({**{n: 0 for n in OTHERS}, "state_errors": 0})
    cmp.state("tenant 0", got, want, BOOST, window)
    return cmp


def _want(acc, nbr=None):
    acc = np.asarray(acc, np.int64)
    nbr = np.zeros_like(acc) if nbr is None else np.asarray(nbr, np.int64)
    return {"acc_lo": acc, "acc_hi": acc, "nbr_lo": nbr, "nbr_hi": nbr,
            "requests": int(acc.sum()) // 2, "taken": 2}


def test_state_that_is_the_replay_s_has_no_errors_and_the_cap_holds():
    acc, nbr = [0, 2, 20, 0], [0, 0, 0, 6]
    cmp = _compare(_state(4, acc, nbr), _want(acc, nbr))
    assert cmp.state_errors == 0 and cmp.first_fault is None
    assert _state(4, acc)["salience"][2] == 1.0


@pytest.mark.parametrize("break_it,errors", [
    (lambda g: g["access_count"].__setitem__(1, 3), 2),    # a count, and the sum
    (lambda g: g["salience"].__setitem__(1, 0.65), 1),     # one boost short
    (lambda g: g["salience"].__setitem__(3, 0.74), 1),     # a neighbour boost over
    (lambda g: g["last_accessed"].__setitem__(1, 0.0), 1),  # touched, never stamped
    (lambda g: g["last_accessed"].__setitem__(1, 30.0), 1),  # before the window
    (lambda g: g["last_accessed"].__setitem__(0, 50.0), 1),  # a trace on an untouched row
    (lambda g: g["salience"].__setitem__(0, 0.62), 1),
], ids=["count", "salience-short", "neighbour-over", "unstamped", "stale",
        "trace", "untouched-salience"])
def test_state_that_is_not_the_replay_s_is_counted_row_by_row(break_it, errors):
    acc, nbr = [0, 2, 20, 0], [0, 0, 0, 6]
    got = _state(4, acc, nbr)
    break_it(got)
    cmp = _compare(got, _want(acc, nbr))
    assert cmp.state_errors == errors and "tenant 0" in cmp.first_fault


def test_state_errors_is_compared_only_where_the_limits_name_it():
    plain = reference.Comparison({n: 0 for n in OTHERS})
    assert list(plain.numbers()) == list(OTHERS)
    named = reference.Comparison({**{n: 0 for n in OTHERS}, "state_errors": 0})
    assert list(named.numbers()) == list(OTHERS) + ["state_errors"]
    named.answers, named.state_errors = 1, 1
    assert named.correct is False


# --------------------------------------------------------- the cells, debug

def test_accepted_cells_compare_what_they_compared():
    res = contracts.debug_run("share.serve", 41, ROOT)
    assert list(res["compared"]) == list(OTHERS) and res["correct"] is True


def test_chat_cell_boosts_every_request_and_its_state_is_the_replay_s():
    res = contracts.debug_run("share.chat", 2**31 + 42, ROOT, traced=True)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res["compared"]) == list(OTHERS) + ["state_errors"]
    assert res["compared"]["state_errors"] == {"value": 0.0, "limit": 0.0}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["dispatch.boost_rows_per_req.chat"] == 5.0
    assert m["dispatch.copies.chat"] == 0.0
    assert m["index.puts_per_dispatch.chat"] == 1.0
    assert m["sched.overlap_pct.chat"] == 0.0
    assert m["device.compiles.chat"] == 0.0


@pytest.mark.parametrize("loop", [{}, {"loop": "closed", "clients": 8,
                                       "query_pool": 64}],
                         ids=["open", "closed-and-wrapping"])
def test_half_the_requests_boost_and_the_others_leave_no_trace(loop):
    res = contracts.debug_run("share.chat", 43, ROOT,
                              mix_override={"boost_share": 0.5, **loop})
    assert res["correct"] is True
    assert res["compared"]["state_errors"]["value"] == 0.0


@pytest.mark.parametrize("fault,override", [
    (faults.boosts_off, {}), (faults.boosts_twice, {}),
    (faults.boosts_other_tenant, {}),
    (faults.unboosted_boosts, {"boost_share": 0.5})],
    ids=lambda f: getattr(f, "__name__", "mix"))
def test_a_broken_boost_is_not_correct_by_state_errors_alone(fault, override):
    res = contracts.debug_run("share.chat", 44, ROOT, sabotage=fault,
                              mix_override=override)
    assert res["correct"] is False
    assert res["compared"]["state_errors"]["value"] > 0
    assert all(res["compared"][n]["value"] <= res["compared"][n]["limit"]
               for n in OTHERS)


def test_a_mix_that_boosts_has_to_bring_the_limit():
    with pytest.raises(KeyError, match="limits"):
        harness.run_cell("share.serve", 1, 0.3, False, root=ROOT, debug=True,
                         mix_override={"boost_share": 1.0})


# ------------------------------------------------------------ the families

def test_every_suffixed_reader_of_this_pr_is_its_family_s():
    for cell, suffix in (("fill.lat", ".flat"), ("share.chat", ".chat")):
        w = harness.cell_files(cell, ROOT)[0]
        names = [m["name"] for m in harness.metrics_of(w, "per_layer", ROOT)]
        assert names and all(n.endswith(suffix) for n in names)
        for n in names:
            assert harness.reader(n, ROOT) is families.FAMILIES[n[:-len(suffix)]]


def test_a_file_that_names_no_family_is_refused_with_a_sentence():
    with pytest.raises(KeyError, match="names no metric family"):
        families.reader_for("/x/benchmark/metrics/sched.nothing.chat.py")
