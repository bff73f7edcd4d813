"""Flax text encoder: determinism, normalization, batching, graft hooks."""

import numpy as np
import pytest

from lazzaro_tpu.models.encoder import EncoderConfig, TextEncoder
from lazzaro_tpu.models.tokenizer import HashTokenizer


@pytest.fixture(scope="module")
def enc():
    return TextEncoder(EncoderConfig.tiny(), seed=0)


def test_hash_tokenizer_deterministic():
    tok = HashTokenizer(vocab_size=1024, max_len=16)
    a = tok.encode("The quick brown fox")
    b = tok.encode("The quick brown fox")
    assert a == b
    assert len(a) == 16
    assert a[0] == 1  # CLS


def test_encoder_outputs_normalized(enc):
    v = enc.encode("hello world")
    assert v.shape == (enc.dim,)
    assert np.linalg.norm(v) == pytest.approx(1.0, abs=1e-4)


def test_encoder_deterministic_across_instances():
    a = TextEncoder(EncoderConfig.tiny(), seed=0).encode("same text")
    b = TextEncoder(EncoderConfig.tiny(), seed=0).encode("same text")
    assert np.allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("forward_tokens", [None, 64])
def test_batch_matches_single(enc, forward_tokens, monkeypatch):
    if forward_tokens:       # 2 rows per forward: the batch spans 2 forwards
        from lazzaro_tpu.models import encoder
        monkeypatch.setattr(encoder, "_FORWARD_TOKENS", forward_tokens)
    texts = ["alpha beta", "gamma delta", "epsilon"]
    batch = enc.encode_batch(texts)
    assert batch.shape == (3, enc.dim)
    for i, t in enumerate(texts):
        assert np.allclose(batch[i], enc.encode(t), atol=1e-5)


def test_encoder_embedder_provider(enc):
    from lazzaro_tpu.core.providers import EncoderEmbedder
    p = EncoderEmbedder(enc)
    assert p.dim == enc.dim
    v = p.embed("test")
    assert len(v) == enc.dim
    assert len(p.batch_embed(["a", "b"])) == 2


def _load_graft():
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "__graft_entry__.py"
    spec = importlib.util.spec_from_file_location("graft_entry", str(path))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


@pytest.fixture()
def cache_placed_outside(monkeypatch, tmp_path):
    """With the variable set the hooks leave jax's config alone (jax read it
    at import, before this fixture), so the suite keeps compiling uncached."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_graft_entry_compiles(cache_placed_outside):
    import jax
    m = _load_graft()
    fn, args = m.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] >= 259  # vocab logits


def test_dryrun_multichip_8(cache_placed_outside):
    _load_graft().dryrun_multichip(8)
