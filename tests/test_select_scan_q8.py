"""The int8 coarse scan that selects while the shadow streams (ISSUE 36;
tier-1, CPU, small pools): ``ops/pallas_topk.blocked_two_tier_q8`` — the
Pallas kernel ``lz_select_scan_q8`` in interpret mode and its plain-JAX twin
— against a dense ``jnp`` coarse top-k over the ``[queries, rows]`` tile the
kernel never builds: both tiers' rows AND scores bit for bit, ties at the
fetch boundary (the lower row wins), dead rows, a block with no super row, a
tenant with no row at all, more queries than a group, and a pool that no
block divides. No number read here is a device number."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lazzaro_tpu.ops import pallas_topk as PT
from lazzaro_tpu.ops.quant import quantize_rows


def dense(q8a, scale, qq, qs, row_main, row_gate, tenant, kf, gf):
    """(gate_s, gate_r, ann_s, ann_r) by one full-width top-k per tier."""
    dots = jax.lax.dot_general(qq, q8a, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)
    sc = dots.astype(jnp.float32) * qs[:, None] * scale[None, :]
    out = []
    for rows, k in ((row_gate, gf), (row_main, kf)):
        s = jnp.where(rows[None, :] == tenant[:, None], sc, PT.NEG)
        v, i = jax.lax.top_k(s, k)
        out += [v, jnp.where(v > PT.NEG / 2, i, q8a.shape[0] - 1)]
    return out


def pool(n, d, c, tenants, seed, ties=False, supers="some", dead=0.1):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    ten = rng.integers(0, tenants, n).astype(np.int32)
    if ties:        # every row twice, in the same tenant: every score twice
        x[n // 2:] = x[:n - n // 2]
        ten[n // 2:] = ten[:n - n // 2]
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    q8a, scale = quantize_rows(jnp.asarray(x, jnp.bfloat16))
    alive = rng.random(n) >= dead
    if supers == "some":
        sup = rng.random(n) < 0.03
    elif supers == "first_block":       # every other block has no super row
        sup = np.zeros(n, bool)
        sup[:300] = rng.random(300) < 0.3
    else:
        sup = np.zeros(n, bool)
    row_main = np.where(alive & ~sup, ten, PT.ROW_DEAD).astype(np.int32)
    row_gate = np.where(alive & sup, ten, PT.ROW_DEAD).astype(np.int32)
    q = rng.standard_normal((c, d)).astype(np.float32)
    qq, qs = quantize_rows(jnp.asarray(q / np.linalg.norm(q, axis=1,
                                                          keepdims=True)))
    # tenant ``tenants`` has no row: its lists stay empty
    tq = rng.integers(0, tenants + 1, c).astype(np.int32)
    return (q8a, scale, qq, qs, jnp.asarray(row_main), jnp.asarray(row_gate),
            jnp.asarray(tq))


CASES = {
    # name: (rows, dim, queries, tenants, k_fetch, g_fetch, pool options)
    "three_blocks": (1536, 64, 5, 3, 136, 9, {}),
    "ties_at_the_boundary": (3072, 64, 6, 3, 136, 9, {"ties": True}),
    "ties_narrow_fetch": (1536, 32, 9, 2, 7, 2, {"ties": True, "dead": 0.0}),
    "block_without_super": (2560, 64, 37, 7, 40, 9,
                            {"supers": "first_block"}),
    "no_super_at_all": (1536, 32, 3, 2, 136, 9, {"supers": "none"}),
    "half_dead": (2048 + 512, 64, 12, 4, 136, 9, {"dead": 0.5}),
    "fetch_wider_than_a_tenant": (1536, 32, 4, 24, 136, 9, {}),
}


@pytest.mark.parametrize("impl", ["jax", "pallas"])
@pytest.mark.parametrize("name", list(CASES))
def test_scan_matches_the_dense_coarse_topk(name, impl):
    n, d, c, tenants, kf, gf, opts = CASES[name]
    assert PT.q8_block_tiles(n, d)
    args = pool(n, d, c, tenants, seed=len(name), **opts)
    want = dense(*args, kf, gf)
    got = PT.blocked_two_tier_q8(*args, kf, gf, impl=impl)
    for w, g, what in zip(want, got, ("gate_s", "gate_r", "ann_s", "ann_r")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{name} {impl} {what}")
    if opts.get("ties"):
        # the boundary really is a tie for some query: the dense top-k's
        # last kept score equals the first it dropped
        dots = dense(*args, min(kf + 1, n), gf)[2]
        assert (np.asarray(dots[:, kf - 1]) == np.asarray(dots[:, kf])).any()


@pytest.mark.parametrize("n", [1000, 640, 4097])
def test_pool_that_no_block_divides_is_one_whole_block(n):
    assert not PT.q8_block_tiles(n, 64)
    args = pool(n, 64, 4, 3, seed=n)
    want = dense(*args, 136, 9)
    got = PT.blocked_two_tier_q8(*args, 136, 9)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    with pytest.raises(ValueError, match="no block tiles"):
        PT.blocked_two_tier_q8(*args, 136, 9, impl="pallas")


def test_more_queries_than_one_call_holds_stream_in_pieces():
    args = pool(1536, 32, PT._MAX_QUERIES + 5, 3, seed=5)
    want = dense(*args, 20, 3)
    got = PT.blocked_two_tier_q8(*args, 20, 3, impl="jax")
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_program_holds_no_queries_by_rows_tile():
    """The serving core's jaxpr: nothing of ``[queries, rows]``, and no
    ``top_k`` over the pool's width."""
    n, d, c = 8192, 64, 16
    args = pool(n, d, c, 3, seed=1)
    text = str(jax.make_jaxpr(
        lambda *a: PT.blocked_two_tier_q8(*a, 136, 9, impl="jax"))(*args))
    assert f"[{c},{n}]" not in text and f"[{c}, {n}]" not in text
    assert "top_k" not in text
