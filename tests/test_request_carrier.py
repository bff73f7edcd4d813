"""The request carrier (ISSUE 37; tier-1, CPU): a serving dispatch stages its
requests as ONE int32 array and one host→device transfer.

- the host half (``utils.batching.RequestCarrier``) and the device half
  (``core.state._unpack_requests``, under ``jit``) agree on every field, bit
  for bit: queries with NaN, signed zeros, denormals and the largest finite,
  tenants −1 and 2³¹−1, pad rows, each scalar, at the serving buckets and at
  a width that is no multiple of 128;
- every family × (read, boosting), and the mesh, serves on a seeded arena
  what the PARENT commit served: ``tests/data/request_carrier_golden.json``
  was written by this file's ``__main__`` run against the parent's checkout
  (``PYTHONPATH=<parent> python tests/test_request_carrier.py <out>``).
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.serve import RetrievalRequest

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "request_carrier_golden.json")
D = 32
KW = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
          nbr_boost=0.02, now=1234.5)
FAMILIES = ("exact", "quant", "tiered", "ivf", "ivf_tiered", "pq",
            "pq_tiered")


# ----------------------------------------------------------- the round trip
SPECIALS = np.array([np.nan, -np.nan, 0.0, -0.0, 1e-45, -1e-45, 1.1754942e-38,
                     np.finfo(np.float32).max, -np.finfo(np.float32).max,
                     np.inf, 1.0], np.float32)


def _carrier(n, dim, bucket):
    from lazzaro_tpu.utils.batching import RequestCarrier
    rng = np.random.default_rng(n * dim)
    q = rng.standard_normal((n, dim)).astype(np.float32)
    q[:, :len(SPECIALS)] = SPECIALS
    q[0, -1] = np.float32(-0.0)
    cols = dict(
        valid=rng.integers(0, 2, n), gate_on=rng.integers(0, 2, n),
        boost_on=rng.integers(0, 2, n), k=rng.integers(1, 129, n),
        cap=rng.integers(0, 6, n), nprobe=rng.integers(0, 9, n),
        tenant=np.r_[-1, 2**31 - 1, rng.integers(0, 1250, n - 2)])
    car = RequestCarrier(n, dim, bucket)
    car.q[:n] = q
    car.fill(**cols)
    return car, q, cols


def _unpacked(car, dim):
    from lazzaro_tpu.core import state as S
    return jax.jit(S._unpack_requests, static_argnums=1)(car.buf, dim)


@pytest.mark.parametrize("dim", [768, 100])
@pytest.mark.parametrize("bucket", [8, 16, 64])
def test_every_field_comes_back_bit_for_bit(bucket, dim):
    n = bucket - 3                      # three pad rows
    car, q, cols = _carrier(n, dim, bucket)
    assert car.buf.shape == (bucket, dim + 11) and car.buf.dtype == np.int32
    car.fill(super_gate=0.4, now=1234.5, acc_boost=0.05,
                    nbr_boost=0.02)
    r = _unpacked(car, dim)
    got_q = np.asarray(r.q)
    assert got_q.dtype == np.float32
    np.testing.assert_array_equal(got_q[:n].view(np.int32), q.view(np.int32))
    for name, field, dt in (("valid", r.q_valid, bool),
                            ("gate_on", r.gate_on, bool),
                            ("boost_on", r.boost_on, bool),
                            ("k", r.k_q, np.int32), ("cap", r.cap_q, np.int32),
                            ("nprobe", r.nprobe_q, np.int32),
                            ("tenant", r.tenant, np.int32)):
        got = np.asarray(field)
        assert got.dtype == dt, name
        np.testing.assert_array_equal(got[:n], cols[name].astype(dt), name)
    # pad rows: invalid, matching no tenant, asking nothing
    assert not got_q[n:].view(np.int32).any()
    assert not np.asarray(r.q_valid)[n:].any()
    assert (np.asarray(r.tenant)[n:] == -1).all()
    for field in (r.k_q, r.cap_q, r.nprobe_q, r.gate_on, r.boost_on):
        assert not np.asarray(field)[n:].any()


@pytest.mark.parametrize("name,value", [
    ("super_gate", 0.4), ("now", 1234.5678), ("acc_boost", 1e-45),
    ("nbr_boost", -0.0)])
def test_each_scalar_comes_back_bit_for_bit(name, value):
    car, _, _ = _carrier(5, 100, 8)
    car.fill(**{name: value})
    r = _unpacked(car, 100)
    for other in ("super_gate", "now", "acc_boost", "nbr_boost"):
        got = np.asarray(getattr(r, other))
        assert got.shape == () and got.dtype == np.float32
        want = np.float32(value if other == name else 0.0)
        assert got.view(np.int32) == want.view(np.int32), other


def test_a_one_row_bucket_carries_the_scalars_too():
    from lazzaro_tpu.utils.batching import RequestCarrier
    car = RequestCarrier.of(np.ones((1, 100), np.float32), valid=[1],
                            tenant=[7], k=[5], super_gate=0.4, now=2.0,
                            acc_boost=0.05, nbr_boost=0.02)
    assert car.buf.shape == (1, 111)
    r = _unpacked(car, 100)
    assert [float(x) for x in (r.super_gate, r.now, r.acc_boost,
                               r.nbr_boost)] == [
        float(np.float32(x)) for x in (0.4, 2.0, 0.05, 0.02)]
    assert int(r.tenant[0]) == 7 and bool(r.q_valid[0])


def test_a_carrier_of_another_width_is_refused_at_trace_time():
    car, _, _ = _carrier(5, 100, 8)
    with pytest.raises(ValueError, match="request carrier"):
        _unpacked(car, 96)


# ------------------------------------------- the answers are the parent's
def _vecs(n, seed):
    nz = np.random.default_rng(seed).standard_normal((n, D)).astype(
        np.float32)
    return nz / np.linalg.norm(nz, axis=1, keepdims=True)


def family_index(fam, mesh=None, **kw):
    """A seeded two-tenant arena of 200 + 40 rows, routed to ``fam``."""
    coarse = fam.startswith(("ivf", "pq"))
    idx = MemoryIndex(
        dim=D, capacity=255, edge_capacity=1024, epoch=1000.0, mesh=mesh, **kw,
        int8_serving=fam in ("quant", "tiered", "ivf_tiered"),
        ivf_nprobe=4 if coarse else 0, pq_serving=fam.startswith("pq"),
        serve_k_max=16, coarse_slack=8)
    emb = _vecs(240, 0)
    for tenant, lo, hi in (("u0", 0, 200), ("u1", 200, 240)):
        ids = [f"n{i}" for i in range(lo, hi)]
        idx.add(ids, emb[lo:hi], [0.5] * len(ids), [0.0] * len(ids),
                ["semantic"] * len(ids), ["default"] * len(ids), tenant,
                is_super=[i % 29 == 0 for i in range(lo, hi)])
        idx.add_edges([(a, b, 0.7) for a, b in zip(ids, ids[1:])], tenant)
    if coarse:
        idx._IVF_MIN_ROWS = 1
        assert idx.ivf_maintenance()
    if fam.endswith("tiered"):
        tm = idx.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0)
        tm.demote_rows([idx.id_to_row[f"n{i}"] for i in range(100, 200)])
        assert tm.cold_count > 90
    return idx, emb


def served(idx, emb, boost):
    """Six requests of mixed k over both tenants, one unknown tenant and
    one query of the wrong width; with ``boost`` the arena's boost columns
    after the dispatch."""
    rng = np.random.default_rng(9)
    reqs = [RetrievalRequest(
        query=emb[i * 7] + 0.01 * rng.standard_normal(D).astype(np.float32),
        tenant=t, k=k, gate_enabled=(i % 2 == 0), boost=boost)
        for i, (t, k) in enumerate((("u0", 4), ("u0", 16), ("u1", 7),
                                    ("nobody", 5), ("u0", 1), ("u0", 10)))]
    reqs.append(RetrievalRequest(query=np.ones(D + 1, np.float32),
                                 tenant="u0", k=5, boost=boost))
    out = {"results": [
        {"ids": list(r.ids), "scores": [float(s) for s in r.scores],
         "gate_id": r.gate_id, "fast": bool(r.fast),
         "boosted": bool(r.boosted)}
        for r in idx.search_fused_requests(reqs, **KW)]}
    if boost:
        st = idx.state
        out["salience"] = np.asarray(st.salience, np.float64).tolist()
        out["access_count"] = np.asarray(st.access_count).tolist()
        out["last_accessed"] = np.asarray(st.last_accessed,
                                          np.float64).tolist()
    return out


def _mesh(n):
    from lazzaro_tpu.parallel.mesh import make_mesh
    return make_mesh(("data",), (n,), devices=jax.devices()[:n])


CASES = [(fam, boost) for fam in FAMILIES for boost in (False, True)] + [
    ("mesh_exact", False), ("mesh_exact", True), ("mesh_quant", False)]


def routed_index(fam, **kw):
    """``family_index`` on one chip, or for ``mesh_<fam>`` on four."""
    mesh = _mesh(4) if fam.startswith("mesh_") else None
    idx, emb = family_index(fam.replace("mesh_", ""), mesh=mesh, **kw)
    mode = idx._serve_route(KW["cap_take"]).mode
    assert mode == fam.replace("mesh_", "sharded_"), mode
    return idx, emb


def _run_case(fam, boost):
    return served(*routed_index(fam), boost)


@pytest.mark.parametrize("fam,boost", CASES)
def test_served_answers_are_the_parents(fam, boost):
    with open(GOLDEN) as f:
        want = json.load(f)[f"{fam}:{int(boost)}"]
    got = _run_case(fam, boost)
    assert len(got["results"]) == len(want["results"]) == 7
    for g, w in zip(got["results"], want["results"]):
        assert g["ids"] == w["ids"]
        np.testing.assert_allclose(g["scores"], w["scores"], rtol=0,
                                   atol=1e-6)
        assert (g["gate_id"], g["fast"], g["boosted"]) == (
            w["gate_id"], w["fast"], w["boosted"])
    assert any(g["ids"] for g in got["results"])
    if boost:
        assert got["access_count"] == want["access_count"]
        for col in ("salience", "last_accessed"):
            np.testing.assert_allclose(got[col], want[col], rtol=0,
                                       atol=1e-6)
        assert sum(got["access_count"]) > 0


if __name__ == "__main__":      # write the golden file, from the PARENT
    golden = {f"{fam}:{int(boost)}": _run_case(fam, boost)
              for fam, boost in CASES}
    with open(sys.argv[1], "w") as f:
        json.dump(golden, f)
