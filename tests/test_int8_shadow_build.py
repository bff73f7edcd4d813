"""The int8 shadow's build and the int8 program's identity (ISSUE 36
satellites; tier-1, CPU, counts and no timing): a (re)build launches the same
number of device programs whatever the arena's size, its program holds an f32
array of a block's shape and none of the arena's, a second call on a clean
index launches nothing; and the int8 serving program lowered for the TPU is
byte for byte the same in two fresh processes, so a warm start finds it in
the persistent compile cache and never compiles it again."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.ops import quant as Q
from lazzaro_tpu.utils.batching import REQUEST_COLS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _index(capacity, d=16):
    idx = MemoryIndex(dim=d, capacity=capacity, int8_serving=True)
    rng = np.random.default_rng(capacity)
    n = 64
    idx.add([f"m{i}" for i in range(n)], rng.standard_normal((n, d)),
            [0.5] * n, [0.0] * n, ["semantic"] * n, ["default"] * n, "u")
    return idx


def _counts(idx):
    tel = idx.telemetry
    return (tel.counter_total("index.shadow_builds"),
            tel.counter_total("index.shadow_dispatches"))


def test_a_rebuild_launches_the_same_programs_at_4096_rows_as_at_65536():
    launched = {}
    for capacity in (4095, 65_536):
        idx = _index(capacity)
        rows = idx.state.salience.shape[0]
        assert rows >= capacity
        before = _counts(idx)
        q8, scale = idx._int8_shadow_for(idx.state)
        assert q8.shape == (rows, 16) and q8.dtype == jnp.int8
        assert scale.shape == (rows,) and scale.dtype == jnp.float32
        built = tuple(b - a for a, b in zip(before, _counts(idx)))
        launched[capacity] = built
        # clean: the same arrays come back and nothing is launched
        again = idx._int8_shadow_for(idx.state)
        assert again[0] is q8 and again[1] is scale
        assert _counts(idx) == tuple(a + b for a, b in zip(before, built))
        assert idx.telemetry.timer_values("index.shadow_ms")
    assert launched[4095] == launched[65_536] == (1, 1)
    # the larger arena really was built in several steps of one program
    assert Q.shadow_block_rows(4096) == 4096
    assert 512 <= Q.shadow_block_rows(69_632) < 69_632


def test_a_write_makes_the_next_call_build_again():
    idx = _index(4095)
    idx._int8_shadow_for(idx.state)
    idx.add(["fresh"], np.ones((1, 16)), [0.5], [0.0], ["semantic"],
            ["default"], "u")
    before = _counts(idx)
    q8, _ = idx._int8_shadow_for(idx.state)
    assert _counts(idx) == (before[0] + 1, before[1] + 1)
    assert np.asarray(q8[idx.id_to_row["fresh"]]).any()


def _shapes(jaxpr, found):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            found.add((tuple(v.aval.shape), str(v.aval.dtype)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, found)
    return found


@pytest.mark.parametrize("rows", [5_001_216, 196_608, 69_632])
def test_build_holds_an_f32_block_and_never_an_f32_arena(rows):
    d = 768
    block = Q.shadow_block_rows(rows)
    assert block % 512 == 0 and rows % block == 0 and block <= 65_536 < rows
    jaxpr = jax.make_jaxpr(Q.quantize_arena)(
        jax.ShapeDtypeStruct((rows, d), jnp.bfloat16))
    found = _shapes(jaxpr.jaxpr, set())
    assert ((block, d), "float32") in found
    # the widest f32 value is a block's; the arena-long f32 vector is the
    # scales, the program's second output
    widest = max(int(np.prod(shape)) for shape, dt in found
                 if dt == "float32")
    assert widest == block * d < rows * d, sorted(found)
    assert ((rows, d), "int8") in found and ((rows,), "float32") in found


@pytest.mark.parametrize("rows", [4096, 1000, 69_632, 70_000])
def test_blocked_build_is_quantize_rows(rows):
    x = jnp.asarray(np.random.default_rng(rows).standard_normal((rows, 8)),
                    jnp.bfloat16)
    want, got = Q.quantize_rows(x), Q.quantize_arena(x)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


KEY = r"""
import hashlib
import jax, jax.numpy as jnp
from lazzaro_tpu.core import state as S
from lazzaro_tpu.ops import pallas_topk as PT
from lazzaro_tpu.utils.batching import REQUEST_COLS
PT.on_tpu = lambda: True            # the TPU's vehicle: the Pallas kernel
rows, d, c = 8192, 64, 16           # the benchmark's debug geometry
sds = jax.ShapeDtypeStruct
st = jax.eval_shape(lambda: S.init_arena(rows - 1, d, jnp.bfloat16))
args = (st, sds((rows, d), jnp.int8), sds((rows,), jnp.float32),
        sds((rows + 1,), jnp.int32), sds((1024,), jnp.int32),
        sds((c, d + REQUEST_COLS), jnp.int32))
text = S.search_fused_quant_ragged_read.trace(
    *args, k=128, slack=8, cap_take=5, max_nbr=8).lower(
        lowering_platforms=("tpu",)).as_text()
assert "lz_select_scan_q8" in text and "tpu_custom_call" in text
print("MODULE", hashlib.sha256(text.encode()).hexdigest(), len(text))
"""


def test_int8_program_is_the_same_module_in_two_fresh_processes():
    """What the persistent cache keys on is the lowered module (and the
    compiler's options): lowered for the TPU — Mosaic payload and all — in
    two processes with different hash seeds, it has to be the same bytes."""
    seen = []
    for hashseed in ("1", "4242"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=hashseed,
                   PYTHONPATH=ROOT)
        p = subprocess.run([sys.executable, "-c", KEY], env=env, cwd=ROOT,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        seen.append([ln for ln in p.stdout.splitlines()
                     if ln.startswith("MODULE")][0])
    assert seen[0] == seen[1]


# --------------------------------------- what every warm start pays again

def _eqns(jaxpr):
    return sum(1 + sum(_eqns(sub) for sub in
                       jax.core.jaxprs_in_params(e.params))
               for e in jaxpr.eqns)


def test_every_bucket_traces_the_kernel_once_a_query_tile(monkeypatch):
    """A warm start finds the executables in the persistent cache but still
    TRACES and LOWERS every serving program to ask for them (PR 35's set-up
    regression, PERF.md section 6): eight batch buckets and two twins are
    sixteen programs. The kernel is a jitted function of its own and the
    buckets pad their queries to one of two int8 tiles, so its body is
    traced twice, not sixteen times — and the whole program stays a few
    hundred equations (a count: the kernel written as eight unrolled panels
    over two unrolled tiers was several times that)."""
    from lazzaro_tpu.core import state as S
    from lazzaro_tpu.ops import pallas_topk as PT
    monkeypatch.setattr(PT, "on_tpu", lambda: True)
    built = []
    real = PT._select_q8_kernel
    monkeypatch.setattr(PT, "_select_q8_kernel",
                        lambda *a: built.append(a) or real(*a))
    rows, d = 8192, 48                  # a geometry no other test traces
    sds = jax.ShapeDtypeStruct
    st = jax.eval_shape(lambda: S.init_arena(rows - 1, d, jnp.bfloat16))
    sizes = []
    for c in range(8, 65, 8):
        traced = S.search_fused_quant_ragged_read.trace(
            st, sds((rows, d), jnp.int8), sds((rows,), jnp.float32),
            sds((rows + 1,), jnp.int32), sds((1024,), jnp.int32),
            sds((c, d + REQUEST_COLS), jnp.int32), k=128, slack=8,
            cap_take=5, max_nbr=8)
        sizes.append(_eqns(traced.jaxpr.jaxpr))
    assert len(built) == 2, built       # queries padded to 32 and to 64
    assert max(sizes) < 1200, sizes
