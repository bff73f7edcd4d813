"""On-device text encoder (bge-base-en-class) in Flax.

Replaces the reference's remote embedding providers (``core/providers.py``
OpenAIEmbedder :36-57, GeminiEmbedder :101-128, TogetherEmbedder :170-196)
with an in-tree JAX forward pass batched onto the MXU in bfloat16.

Two architectures, selected by ``EncoderConfig.arch``:

- ``"pre_ln"`` (default): pre-LayerNorm transformer, mean pooling — the
  compact in-tree geometry for random-weight / from-scratch use.
- ``"bert"``: post-LayerNorm HF-BERT numerics (eps 1e-12, exact GELU, CLS
  pooling) — bit-compatible with bge-base-en-class checkpoints.
  ``TextEncoder.from_hf`` maps a ``transformers`` BertModel's weights
  directly into this module (token-type embeddings folded into position
  embeddings, torch Linear kernels transposed), so a locally available real
  checkpoint drops in with zero egress.

Weights are deterministic random by default (no egress to fetch checkpoints);
``load_params`` restores an Orbax checkpoint for real deployments. Batch data
parallelism over a mesh 'data' axis is a one-line sharding constraint because
the forward pass is purely functional.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from lazzaro_tpu.models.tokenizer import HashTokenizer, PAD_ID

# Tokens per encoder forward (``TextEncoder.encode_batch``): 128 rows at
# L=512, where the bge-base forward takes 2.0 GB of temporaries on a v5e.
_FORWARD_TOKENS = 65_536


@dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 32768
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 128
    dtype: str = "bfloat16"
    arch: str = "pre_ln"      # "pre_ln" | "bert" (HF post-LN numerics)
    pooling: str = "mean"     # "mean" | "cls" (bge-class uses CLS)

    @staticmethod
    def tiny() -> "EncoderConfig":
        return EncoderConfig(vocab_size=1024, hidden=64, layers=2, heads=2,
                             mlp_dim=128, max_len=32, dtype="float32")

    @staticmethod
    def base() -> "EncoderConfig":
        return EncoderConfig()

    @staticmethod
    def bge_base() -> "EncoderConfig":
        """bge-base-en-v1.5 geometry (BERT-base, CLS pooling)."""
        return EncoderConfig(vocab_size=30522, hidden=768, layers=12,
                             heads=12, mlp_dim=3072, max_len=512,
                             dtype="float32", arch="bert", pooling="cls")


class EncoderBlock(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x, mask):
        dt = jnp.dtype(self.cfg.dtype)
        h = nn.LayerNorm(dtype=dt)(x)
        h = nn.MultiHeadDotProductAttention(
            num_heads=self.cfg.heads, dtype=dt, qkv_features=self.cfg.hidden,
        )(h, h, mask=mask)
        x = x + h
        h = nn.LayerNorm(dtype=dt)(x)
        h = nn.Dense(self.cfg.mlp_dim, dtype=dt)(h)
        h = nn.gelu(h)
        h = nn.Dense(self.cfg.hidden, dtype=dt)(h)
        return x + h


def _pool_and_normalize(x, pad_mask, pooling: str):
    """[B, L, H] hidden states → [B, H] f32 L2-normalized sentence vector."""
    if pooling == "cls":
        pooled = x.astype(jnp.float32)[:, 0]
    else:
        m = pad_mask[..., None].astype(jnp.float32)
        pooled = (x.astype(jnp.float32) * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-9)


class Encoder(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, token_ids):
        """token_ids [B, L] int32 → [B, hidden] f32, L2-normalized."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        pad_mask = token_ids != PAD_ID                        # [B, L]
        x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=dt)(token_ids)
        pos = nn.Embed(cfg.max_len, cfg.hidden, dtype=dt)(
            jnp.arange(token_ids.shape[1])[None, :])
        x = x + pos
        attn_mask = pad_mask[:, None, None, :] & pad_mask[:, None, :, None]
        for _ in range(cfg.layers):
            x = EncoderBlock(cfg)(x, attn_mask)
        x = nn.LayerNorm(dtype=dt)(x)
        return _pool_and_normalize(x, pad_mask, self.cfg.pooling)


LN_EPS_BERT = 1e-12


class BertLayer(nn.Module):
    """One HF-BERT encoder layer: post-LN, exact GELU, eps 1e-12."""
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x, pad_mask):
        from lazzaro_tpu.ops.flash_attention import reference_attention
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        B, L, H = x.shape
        nh = cfg.heads
        dh = H // nh
        q = nn.Dense(H, dtype=dt, name="q")(x).reshape(B, L, nh, dh)
        k = nn.Dense(H, dtype=dt, name="k")(x).reshape(B, L, nh, dh)
        v = nn.Dense(H, dtype=dt, name="v")(x).reshape(B, L, nh, dh)
        # Same canonical einsum formulation the decoder and flash VJP use;
        # keys masked by padding, queries unmasked (HF semantics).
        ctx = reference_attention(q, k, v, pad_mask[:, None, :])
        ctx = ctx.reshape(B, L, H)
        h = nn.Dense(H, dtype=dt, name="attn_out")(ctx)
        x = nn.LayerNorm(epsilon=LN_EPS_BERT, dtype=dt, name="attn_ln")(x + h)
        h = nn.Dense(cfg.mlp_dim, dtype=dt, name="ffn_in")(x)
        h = nn.gelu(h, approximate=False)          # HF "gelu" is erf-exact
        h = nn.Dense(H, dtype=dt, name="ffn_out")(h)
        return nn.LayerNorm(epsilon=LN_EPS_BERT, dtype=dt, name="ffn_ln")(x + h)


class BertEncoder(nn.Module):
    """HF-BertModel-compatible encoder (``TextEncoder.from_hf`` fills the
    params from a transformers checkpoint; token-type embeddings are folded
    into ``pos_emb`` since every input is segment 0)."""
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, token_ids, return_hidden: bool = False):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        pad_mask = token_ids != PAD_ID                        # [B, L]
        x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=dt,
                     name="word_emb")(token_ids)
        x = x + nn.Embed(cfg.max_len, cfg.hidden, dtype=dt, name="pos_emb")(
            jnp.arange(token_ids.shape[1])[None, :])
        x = nn.LayerNorm(epsilon=LN_EPS_BERT, dtype=dt, name="emb_ln")(x)
        for i in range(cfg.layers):
            x = BertLayer(cfg, name=f"layer_{i}")(x, pad_mask)
        if return_hidden:
            return x
        return _pool_and_normalize(x, pad_mask, cfg.pooling)


class TextEncoder:
    """Host-facing wrapper: tokenizer + jitted batched forward with
    power-of-two batch bucketing (static shapes, bounded compile cache)."""

    def __init__(self, cfg: Optional[EncoderConfig] = None, seed: int = 0,
                 tokenizer: Optional[HashTokenizer] = None,
                 init_params: bool = True):
        self.cfg = cfg or EncoderConfig.base()
        self.tokenizer = tokenizer or HashTokenizer(self.cfg.vocab_size, self.cfg.max_len)
        # The pad mask is ``token_ids != PAD_ID`` (PAD_ID=0) in both encoder
        # archs; a tokenizer whose pad id differs would silently corrupt
        # attention (pads attended, vocab row 0 masked everywhere).
        tok_pad = getattr(self.tokenizer, "pad_id", PAD_ID)
        if tok_pad != PAD_ID:
            raise ValueError(
                f"tokenizer pad id {tok_pad} != {PAD_ID}; the encoder masks "
                f"token id {PAD_ID} as padding — use a vocab with [PAD] at row 0")
        cls = BertEncoder if self.cfg.arch == "bert" else Encoder
        self.model = cls(self.cfg)
        if init_params:
            dummy = jnp.zeros((1, self.cfg.max_len), jnp.int32)
            self.params = self.model.init(jax.random.PRNGKey(seed), dummy)
        else:
            self.params = None        # caller installs params (from_hf)
        self._apply = jax.jit(self.model.apply)

    @classmethod
    def from_hf(cls, hf_model, tokenizer=None, pooling: str = "cls",
                max_len: int = 128,
                vocab_file: Optional[str] = None) -> "TextEncoder":
        """Build a ``BertEncoder``-backed TextEncoder from a local
        ``transformers`` BertModel (bge-base-en-class) — no egress, the
        checkpoint must already be on disk/in memory.

        ``tokenizer``: anything with ``batch_encode(texts, max_len) ->
        List[List[int]]``; pass ``HFTokenizerAdapter(hf_tok, max_len)`` for
        a live transformers tokenizer, or give ``vocab_file`` (the
        checkpoint's ``vocab.txt``) to use the in-tree WordPiece tokenizer
        (HF-id-exact, ``models/wordpiece.py``). Defaults to the hash
        tokenizer (fine for smoke tests, wrong vocab for real retrieval).
        """
        if tokenizer is not None and vocab_file is not None:
            raise ValueError("pass either tokenizer or vocab_file, not both")
        if vocab_file is not None:
            from lazzaro_tpu.models.wordpiece import WordPieceTokenizer
            tokenizer = WordPieceTokenizer.from_vocab_file(
                vocab_file, max_len=max_len)
        hc = hf_model.config
        tok_vocab = getattr(tokenizer, "vocab_size", None)
        if tok_vocab is not None and tok_vocab > hc.vocab_size:
            raise ValueError(
                f"tokenizer vocab_size {tok_vocab} exceeds checkpoint "
                f"vocab_size {hc.vocab_size}; out-of-range ids would produce "
                f"silent NaN embeddings (Flax Embed OOB lookup)")
        cfg = EncoderConfig(
            vocab_size=hc.vocab_size, hidden=hc.hidden_size,
            layers=hc.num_hidden_layers, heads=hc.num_attention_heads,
            mlp_dim=hc.intermediate_size,
            max_len=min(max_len, hc.max_position_embeddings),
            dtype="float32", arch="bert", pooling=pooling)
        enc = cls(cfg, tokenizer=tokenizer, init_params=False)
        enc.params = {"params": bert_params_from_hf(hf_model, cfg)}
        if hasattr(enc.tokenizer, "max_len"):
            enc.tokenizer.max_len = cfg.max_len    # keep pos table in range
        return enc

    @property
    def dim(self) -> int:
        return self.cfg.hidden

    def load_params(self, ckpt_dir: str) -> None:
        import orbax.checkpoint as ocp
        self.params = ocp.StandardCheckpointer().restore(ckpt_dir, self.params)

    def save_params(self, ckpt_dir: str) -> None:
        import orbax.checkpoint as ocp
        ocp.StandardCheckpointer().save(ckpt_dir, self.params)

    def encode_batch(self, texts) -> np.ndarray:
        if not texts:
            return np.zeros((0, self.dim), np.float32)
        # Always tokenize to cfg.max_len: longer rows would index past the
        # position table (Flax Embed fills OOB lookups with NaN, silently).
        from lazzaro_tpu.utils.batching import pad_to_pow2

        ids = np.asarray(
            self.tokenizer.batch_encode(list(texts), self.cfg.max_len),
            np.int32)
        # One forward holds [rows, heads, L, L] attention logits: 1,024 rows
        # of the bge-base geometry (L=512, f32) need 15.8 GB of temporaries
        # and a 16 GB chip refuses the program, so a big batch goes through
        # in forwards of at most _FORWARD_TOKENS tokens each.
        rows = max(1, _FORWARD_TOKENS // self.cfg.max_len)
        outs = []
        for i in range(0, ids.shape[0], rows):
            chunk = ids[i:i + rows]
            outs.append(self._apply(self.params,
                                    jnp.asarray(pad_to_pow2(chunk)))[:len(chunk)])
        return np.concatenate([np.asarray(o, np.float32) for o in outs])

    def encode(self, text: str) -> np.ndarray:
        return self.encode_batch([text])[0]


def make_encoder_train_step(cfg: EncoderConfig, optimizer,
                            mesh=None, temperature: float = 0.05):
    """In-batch-negatives InfoNCE train step (the standard bge/SimCSE
    recipe): a batch of (query, positive) token-id pairs; each query's
    positive is the diagonal, every other row is a negative. Returns
    ``step(params, opt_state, q_ids, p_ids) -> (params, opt_state, loss)``,
    jitted, with batch data-parallelism over the mesh 'data' axis when one
    is given. Lets users fine-tune the retrieval encoder on their own
    memory corpus — a capability the reference cannot have (its embedders
    are remote APIs, providers.py:36-57)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cls = BertEncoder if cfg.arch == "bert" else Encoder
    model = cls(cfg)

    def loss_fn(params, q_ids, p_ids):
        q = model.apply(params, q_ids)        # [B, H], L2-normalized
        p = model.apply(params, p_ids)
        logits = (q @ p.T) / temperature      # [B, B]
        labels = jnp.arange(q.shape[0])
        logp = jax.nn.log_softmax(logits, axis=-1)
        # Symmetric: query→passage and passage→query.
        loss_qp = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        logp_t = jax.nn.log_softmax(logits.T, axis=-1)
        loss_pq = -jnp.take_along_axis(logp_t, labels[:, None], axis=-1).mean()
        return (loss_qp + loss_pq) / 2

    def step(params, opt_state, q_ids, p_ids):
        if mesh is not None:
            sh = NamedSharding(mesh, P("data", None))
            q_ids = jax.lax.with_sharding_constraint(q_ids, sh)
            p_ids = jax.lax.with_sharding_constraint(p_ids, sh)
        loss, grads = jax.value_and_grad(loss_fn)(params, q_ids, p_ids)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda a, u: a + u, params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


class HFTokenizerAdapter:
    """Duck-types ``batch_encode`` over a HuggingFace tokenizer so a real
    WordPiece vocab can drive ``TextEncoder`` (``from_hf``)."""

    def __init__(self, hf_tokenizer, max_len: int = 128):
        self.hf = hf_tokenizer
        self.max_len = max_len

    @property
    def pad_id(self) -> int:
        """Surfaced so TextEncoder's pad-mask guard sees the real pad id
        (BERT-family = 0; a RoBERTa-style pad_token_id=1 must be rejected)."""
        pad = getattr(self.hf, "pad_token_id", 0)
        return 0 if pad is None else int(pad)

    @property
    def vocab_size(self) -> int:
        return int(len(self.hf))

    def batch_encode(self, texts, max_len: Optional[int] = None):
        out = self.hf(list(texts), padding="max_length", truncation=True,
                      max_length=max_len or self.max_len)
        return out["input_ids"]

    def encode(self, text: str, max_len: Optional[int] = None):
        return self.batch_encode([text], max_len)[0]


def bert_params_from_hf(hf_model, cfg: EncoderConfig) -> dict:
    """Map a torch ``transformers`` BertModel state_dict onto ``BertEncoder``
    params: torch Linear kernels are [out, in] → transposed; token-type
    embedding row 0 is folded into the position table (all inputs are
    segment 0, so the sums are identical)."""
    # .float() first: bf16 torch tensors do not support .numpy().
    sd = {k: np.asarray(v.detach().cpu().float().numpy())
          for k, v in hf_model.state_dict().items()}

    def dense(prefix):
        return {"kernel": sd[f"{prefix}.weight"].T.copy(),
                "bias": sd[f"{prefix}.bias"]}

    def ln(prefix):
        return {"scale": sd[f"{prefix}.weight"], "bias": sd[f"{prefix}.bias"]}

    pos = sd["embeddings.position_embeddings.weight"][:cfg.max_len].copy()
    pos += sd["embeddings.token_type_embeddings.weight"][0]
    params = {
        "word_emb": {"embedding": sd["embeddings.word_embeddings.weight"]},
        "pos_emb": {"embedding": pos},
        "emb_ln": ln("embeddings.LayerNorm"),
    }
    for i in range(cfg.layers):
        a = f"encoder.layer.{i}"
        params[f"layer_{i}"] = {
            "q": dense(f"{a}.attention.self.query"),
            "k": dense(f"{a}.attention.self.key"),
            "v": dense(f"{a}.attention.self.value"),
            "attn_out": dense(f"{a}.attention.output.dense"),
            "attn_ln": ln(f"{a}.attention.output.LayerNorm"),
            "ffn_in": dense(f"{a}.intermediate.dense"),
            "ffn_out": dense(f"{a}.output.dense"),
            "ffn_ln": ln(f"{a}.output.LayerNorm"),
        }
    return jax.tree_util.tree_map(jnp.asarray, params)
