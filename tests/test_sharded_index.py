"""ShardedMemoryIndex on the 8-device mesh: placement, search, isolation."""

import numpy as np
import pytest

from lazzaro_tpu.parallel.index import ShardedMemoryIndex
from lazzaro_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(("data",), (8,))


def basis(dim, i):
    v = np.zeros(dim, np.float32)
    v[i % dim] = 1.0
    return v


def test_add_search_roundtrip(mesh):
    idx = ShardedMemoryIndex(mesh, dim=32, capacity=256, dtype=np.float32)
    ids = [f"n{i}" for i in range(10)]
    embs = np.stack([basis(32, i) for i in range(10)])
    idx.add(ids, embs, "alice")
    got, scores = idx.search(basis(32, 4), "alice")
    assert got[0] == "n4"
    assert scores[0] == pytest.approx(1.0, abs=1e-5)


def test_tenant_affinity_placement(mesh):
    idx = ShardedMemoryIndex(mesh, dim=16, capacity=256, tenant_affinity=True)
    idx.add(["a1", "a2"], np.stack([basis(16, 1), basis(16, 2)]), "alice")
    idx.add(["b1"], basis(16, 3).reshape(1, -1), "bob")
    parts_a = {idx.partition_of("a1"), idx.partition_of("a2")}
    assert len(parts_a) == 1  # same home partition
    # bob may or may not share alice's partition (hash), but placement is stable
    assert idx.partition_of("b1") == abs(hash("bob")) % 8


def test_tenant_isolation_and_delete(mesh):
    idx = ShardedMemoryIndex(mesh, dim=16, capacity=256)
    idx.add(["a"], basis(16, 5).reshape(1, -1), "u1")
    idx.add(["b"], basis(16, 5).reshape(1, -1), "u2")
    got, _ = idx.search(basis(16, 5), "u1")
    assert got == ["a"]
    idx.delete(["a"])
    got, _ = idx.search(basis(16, 5), "u1")
    assert got == []


def test_spill_when_home_partition_full(mesh):
    idx = ShardedMemoryIndex(mesh, dim=16, capacity=64)  # 8 rows per partition
    n = 20  # > one partition
    ids = [f"x{i}" for i in range(n)]
    embs = np.stack([basis(16, i) for i in range(n)])
    idx.add(ids, embs, "carol")
    # everything searchable despite spilling across partitions
    got, _ = idx.search(basis(16, 13), "carol")
    assert "x13" in got


def test_decay_tenant_scoped(mesh):
    idx = ShardedMemoryIndex(mesh, dim=16, capacity=64)
    idx.add(["a"], basis(16, 0).reshape(1, -1), "u1", saliences=[0.9])
    idx.add(["b"], basis(16, 1).reshape(1, -1), "u2", saliences=[0.9])
    idx.decay("u1", rate=0.01, floor=0.2)
    sal = np.asarray(idx.salience)
    assert sal[idx.id_to_row["a"]] == pytest.approx(0.893, abs=1e-5)
    assert sal[idx.id_to_row["b"]] == pytest.approx(0.9, abs=1e-6)


def test_pallas_topk_interpret():
    import jax.numpy as jnp
    from lazzaro_tpu.ops.pallas_topk import masked_topk
    N, d, Q, K = 4096 * 2, 128, 8, 10
    rng = np.random.RandomState(3)
    emb = rng.randn(N, d).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    mask = np.ones(N, bool)
    mask[::5] = False
    qs = rng.randn(Q, d).astype(np.float32)
    s, i = masked_topk(jnp.asarray(emb), jnp.asarray(mask), jnp.asarray(qs),
                       K, impl="pallas")
    i = np.asarray(i)
    ref = np.where(mask[None, :], qs @ emb.T, -np.inf)
    for r in range(Q):
        assert list(i[r]) == list(np.argsort(-ref[r], kind="stable")[:K])
