"""What keeps a CPU run from passing for a chip run (ISSUE 21): the compile
cache is placed from outside, one helper decides compiled-vs-interpreted,
``chip_smoke.py`` refuses to produce a result without a TPU, its data is a
pure function of the seed, the driver hooks never re-provision devices, and
packed readbacks ride an int32 carrier a TPU cannot flush."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lazzaro_tpu.core import state as S
from lazzaro_tpu.ops import backend
from lazzaro_tpu.utils import compile_cache
from lazzaro_tpu.utils.batching import fetch_packed, unpack_retrieval

REPO = Path(__file__).resolve().parents[1]


def _load(name):
    spec = importlib.util.spec_from_file_location(name, str(REPO / f"{name}.py"))
    m = importlib.util.module_from_spec(spec)
    sys.modules[name] = m          # dataclasses resolve their module by name
    spec.loader.exec_module(m)
    return m


@pytest.fixture()
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_compile_cache_env_wins(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert config_updates == []        # jax reads the variable itself


def test_compile_cache_fixed_path_under_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.place_compile_cache()
    second = compile_cache.place_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", first)] * 2


def test_on_tpu_is_the_interpret_switch(monkeypatch):
    from lazzaro_tpu.ops import flash_attention as fa

    assert backend.on_tpu() is False               # the suite runs on CPU
    assert fa._resolve(128, 128, 64, 64, None)[4] is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.on_tpu() is True
    assert fa._resolve(128, 128, 64, 64, None)[4] is False


def _run_smoke(cache_dir, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    return subprocess.run([sys.executable, str(REPO / "chip_smoke.py"), *args],
                          env=env, cwd=str(REPO), capture_output=True,
                          text=True, timeout=600)


def test_chip_smoke_refuses_cpu(tmp_path):
    proc = _run_smoke(tmp_path, "--seed", "0")
    assert proc.returncode not in (0, 3)
    assert "needs a TPU" in proc.stderr and "platform='cpu'" in proc.stderr
    assert proc.stdout.strip() == ""               # no result of any kind


def test_chip_smoke_debug_run_is_not_a_result(tmp_path):
    """The tiny store phase passes its own checks on the CPU, and still
    exits 3 without the JSON line."""
    proc = _run_smoke(tmp_path, "--seed", "0", "--cpu-debug", "--only", "store")
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "Not a result." in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    cs = _load("chip_smoke")
    dev = jax.devices()[0]
    out = json.loads(cs.result_line(dev, len(jax.devices())))
    assert out == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    assert isinstance(out["device"]["count"], int)


def test_chip_smoke_data_is_a_pure_function_of_the_seed():
    cs = _load("chip_smoke")
    sz = cs.Sizes.tiny()
    a = cs.tenant_corpus(3, 1, sz.tenant_facts, sz.dim)
    np.testing.assert_array_equal(
        a, cs.tenant_corpus(3, 1, sz.tenant_facts, sz.dim))
    assert not np.array_equal(a, cs.tenant_corpus(4, 1, sz.tenant_facts, sz.dim))
    assert not np.array_equal(a, cs.tenant_corpus(3, 2, sz.tenant_facts, sz.dim))
    np.testing.assert_allclose(np.linalg.norm(a, axis=1), 1.0, atol=1e-6)
    # the seeded near-duplicates clear the 0.95 dedup gate, group mates don't
    d = np.nonzero(cs.is_dup(np.arange(len(a))))[0]
    assert (np.sum(a[d] * a[d - 1], axis=1) > 0.96).all()
    stride = sz.tenant_facts // cs.GROUP
    assert 0.5 < float(a[0] @ a[stride]) < 0.95
    emb = cs.SeededEmbedder(3, sz)
    assert emb.embed(cs.fact_text(1, 7)) == a[7].tolist()
    assert emb.embed("free text") == cs.SeededEmbedder(3, sz).embed("free text")
    llm = cs.SeededLLM(sz)
    ask = [{"role": "system", "content": "extract"},
           {"role": "user", "content": "transcript of conversation 1.0"}]
    assert (llm.completion(ask, {"type": "json_object"})
            == cs.conversation_payload(1, 0, sz))


def test_dryrun_multichip_raises_with_too_few_devices():
    graft = _load("__graft_entry__")
    n = len(jax.devices()) + 8
    with pytest.raises(RuntimeError, match=f"needs {n} devices"):
        graft.dryrun_multichip(n)


def test_packed_readbacks_ride_an_int32_carrier():
    """Row ids below 2**23 are denormal bit patterns as f32 and a TPU
    flushes them to zero; scores ride bitcast inside int32 instead."""
    f = np.array([0.25, -1e30, 3.0e-39, 1.0], np.float32)
    i = np.array([0, 1, 7, 131_071], np.int32)
    from lazzaro_tpu.utils.batching import _packer
    assert _packer((False, True))(jnp.asarray(f), jnp.asarray(i)).dtype == jnp.int32
    got_f, got_i = fetch_packed(jnp.asarray(f), jnp.asarray(i))
    np.testing.assert_array_equal(got_f.view(np.int32), f.view(np.int32))
    np.testing.assert_array_equal(got_i, i)

    k = 4
    ann_s = jnp.asarray(np.linspace(0.9, 0.1, 2 * k, dtype=np.float32).reshape(2, k))
    ann_r = jnp.asarray(np.arange(2 * k, dtype=np.int32).reshape(2, k))
    packed = S._pack_retrieval(jnp.asarray([0.5, 0.25], jnp.float32),
                               jnp.asarray([3, 9], jnp.int32), ann_s, ann_r,
                               jnp.asarray([True, False]))
    assert packed.dtype == jnp.int32
    gate_s, gate_r, s, r, fast, counters = unpack_retrieval(np.asarray(packed), k)
    np.testing.assert_array_equal(gate_s, [0.5, 0.25])
    np.testing.assert_array_equal(gate_r, [3, 9])
    np.testing.assert_array_equal(s, np.asarray(ann_s))
    np.testing.assert_array_equal(r, np.asarray(ann_r))
    np.testing.assert_array_equal(fast, [True, False])
    np.testing.assert_array_equal(counters[:, 0], [k, k])


def _with_near_dup(n=16, d=32):
    rng = np.random.default_rng(0)
    v = rng.standard_normal((n, d)).astype(np.float32)
    v[9] = v[8] + 0.1 * rng.standard_normal(d).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


@pytest.mark.parametrize("n_dev", [1, 2])
def test_deduped_fact_leaves_no_live_scratch_row(n_dev):
    """Found by the smoke's reference check: the duplicate's scatter lands on
    the sentinel row, which used to stay alive and tenant-tagged — it then
    took a top-k slot as an id-less hit and the request came back one
    result short."""
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh
    from lazzaro_tpu.serve.scheduler import RetrievalRequest

    v = _with_near_dup()
    ids = [f"a{i}" for i in range(len(v))]
    if n_dev == 1:
        idx = MemoryIndex(v.shape[1], capacity=255, dtype=jnp.float32)
        pend = idx.ingest_batch_dedup(
            v, [0.5] * 16, [0.0] * 16, ["semantic"] * 16, ["default"] * 16,
            tenant="u", dedup_gate=0.95, chain_weight=0.5, link_k=3,
            link_gate=0.5, link_scale=0.8, shard_modes=(1, 0), now=0.0,
            link_accept_hint=1.0)
        assert list(np.nonzero(pend["dup"])[0]) == [9]
        idx.commit_ingest_dedup(
            pend, [None if pend["dup"][i] else ids[i] for i in range(16)])
        got = idx.search(v[8], "u", k=3)[0]
    else:
        mesh = make_mesh(("data",), (n_dev,), devices=jax.devices()[:n_dev])
        idx = ShardedMemoryIndex(mesh, v.shape[1], capacity=255,
                                 dtype=jnp.float32, cap_take=3)
        assert idx.ingest(ids, v, "u")["merged"] == {"a9": "a8"}
        got = idx.serve_requests(
            [RetrievalRequest(query=v[8], tenant="u", k=3)])[0].ids
    assert not bool(np.asarray(idx.state.alive)[-1])
    assert len(got) == 3 and got[0] == "a8"
