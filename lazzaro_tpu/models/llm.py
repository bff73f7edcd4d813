"""In-tree decoder LM (Gemma-class) for on-TPU consolidation and chat.

The reference delegates every completion to remote HTTP APIs
(``core/providers.py`` OpenAILLM :5-34, GeminiLLM :59-99, TogetherLLM
:130-168). Here the LLM is a first-class TPU model: RoPE + grouped-query
attention + RMSNorm + GeGLU, tied embeddings, byte-level tokenizer (lossless,
zero assets), KV-cache greedy/temperature decoding under ``lax.while_loop``,
and an optax train step.

Parallelism: ``param_specs`` maps every parameter to a PartitionSpec over a
('data', 'model') mesh — embeddings sharded on vocab, attention on heads, MLP
on the hidden axis — so the same model runs single-chip or pjit-sharded
across a pod. Long sequences can route attention through
``lazzaro_tpu.parallel.ring_attention`` (sequence parallelism over ppermute).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from lazzaro_tpu.models.tokenizer import ByteTokenizer
from lazzaro_tpu.ops.backend import on_tpu


@dataclass(frozen=True)
class LMConfig:
    # Byte tokenizer needs 259 ids; padded to 512 so the embedding table
    # shards cleanly over the tensor-parallel mesh axis.
    vocab_size: int = 512
    hidden: int = 2048
    layers: int = 18
    heads: int = 8
    kv_heads: int = 2
    head_dim: int = 256
    mlp_dim: int = 8192
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    # Cache-less full-sequence attention (training forward / logits_for):
    # "xla" = einsum + materialized scores; "flash" = Pallas fused online-
    # softmax kernel with a fused LSE-recompute backward
    # (ops/flash_attention.py) — GQA-aware, causal-skipping, O(T·D) peak HBM
    # in BOTH directions. "auto" (default) resolves to flash on TPU and xla
    # elsewhere. generate()'s prefill/decode passes a KV cache and always
    # uses "xla". Flash is a single-device kernel: explicit "flash" with a
    # >1 'model' mesh axis raises; "auto" falls back to xla there. Layers
    # needing softcap/sliding-window/custom query scale (Gemma-2) fall back
    # to the XLA path automatically.
    attn_impl: str = "auto"
    # --- Gemma-2 family features (all off by default = Gemma-1 numerics) ---
    attn_softcap: float = 0.0     # cap·tanh(scores/cap) on attention logits
    final_softcap: float = 0.0    # cap·tanh(logits/cap) on the LM head
    sliding_window: int = 0       # >0: EVEN layers attend locally (HF layout)
    query_scale: float = 0.0      # 0 → 1/sqrt(head_dim); Gemma-2 uses
                                  # query_pre_attn_scalar**-0.5
    post_norms: bool = False      # pre+post RMSNorm around attn AND mlp

    @staticmethod
    def tiny() -> "LMConfig":
        return LMConfig(hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16,
                        mlp_dim=128, max_seq=128, dtype="float32")

    @staticmethod
    def small() -> "LMConfig":
        return LMConfig(hidden=512, layers=6, heads=8, kv_heads=2, head_dim=64,
                        mlp_dim=2048, max_seq=1024)

    @staticmethod
    def base2b() -> "LMConfig":
        """Gemma-2-2B geometry + numerics (byte vocab): softcapping, pre+post
        norms, alternating local/global attention — the SURVEY §7.5
        north-star consolidation-LM class."""
        return LMConfig(hidden=2304, layers=26, heads=8, kv_heads=4,
                        head_dim=256, mlp_dim=9216, max_seq=4096,
                        attn_softcap=50.0, final_softcap=30.0,
                        sliding_window=4096, post_norms=True)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, T, H, D]; positions: [B, T]."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class Attention(nn.Module):
    cfg: LMConfig
    local: bool = False      # sliding-window layer (Gemma-2 alternation)
    # Sequence parallelism: when set, cache-less attention runs as ring
    # attention over ``seq_axis`` (ppermute ring, O(T/n·d) memory per chip),
    # composed with data parallelism over ``dp_axis``. Long context is a
    # first-class property of the model, not just a standalone kernel.
    seq_mesh: Optional[Mesh] = None
    seq_axis: str = "sp"
    dp_axis: Optional[str] = "data"

    @nn.compact
    def __call__(self, x, positions, cache: Optional[Dict] = None):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        B, T, _ = x.shape
        q = nn.DenseGeneral((cfg.heads, cfg.head_dim), axis=-1, use_bias=False,
                            dtype=dt, name="q")(x)
        k = nn.DenseGeneral((cfg.kv_heads, cfg.head_dim), axis=-1, use_bias=False,
                            dtype=dt, name="k")(x)
        v = nn.DenseGeneral((cfg.kv_heads, cfg.head_dim), axis=-1, use_bias=False,
                            dtype=dt, name="v")(x)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

        if cache is None and self.seq_mesh is not None:
            # Same guard as make_seq_parallel_train_step, enforced HERE so a
            # direct Decoder(cfg, seq_mesh=...) with a Gemma-2 config can
            # never produce silently wrong logits (ring implements standard
            # scaled-dot-product attention only).
            if cfg.attn_softcap or cfg.sliding_window or cfg.query_scale:
                raise ValueError(
                    "ring attention supports standard scaled-dot-product "
                    "attention only (no softcap/sliding-window/query_scale)")
            from lazzaro_tpu.parallel.ring_attention import make_ring_attention
            ring = make_ring_attention(self.seq_mesh, self.seq_axis,
                                       batch_axis=self.dp_axis)
            # K/V go through the ring at Hkv heads; the GQA repeat happens
            # per block inside the ring, so ppermute traffic and per-chip KV
            # memory stay O(T/n · Hkv · D), not O(T/n · H · D).
            out = ring(q, k, v).astype(dt)
            new_cache = None
        elif cache is None and self._use_flash():
            from lazzaro_tpu.ops.flash_attention import flash_attention
            out = flash_attention(q, k, v).astype(dt)   # [B,T,H,D], GQA inside
            new_cache = None
        elif cache is not None:
            # Prefill/decode: scatter this call's K/V rows into the cache at
            # their positions, then attend over the whole cache with a
            # causal-vs-position mask.
            batch_idx = jnp.arange(B)[:, None]                 # [B, 1]
            ck = cache["k"].at[batch_idx, positions].set(k.astype(dt))
            cv = cache["v"].at[batch_idx, positions].set(v.astype(dt))
            new_cache = {"k": ck, "v": cv}
            kv_len = ck.shape[1]
            kv_pos = jnp.arange(kv_len)[None, None, :]          # [1, 1, S]
            attn_mask = kv_pos <= positions[:, :, None]         # [B, T, S]
            if self.local:
                attn_mask &= kv_pos > positions[:, :, None] - cfg.sliding_window
            out = self._xla_attention(q, ck, cv, attn_mask)
        else:
            new_cache = None
            causal = jnp.tril(jnp.ones((T, T), bool))
            if self.local:
                row = jnp.arange(T)[:, None]
                causal &= jnp.arange(T)[None, :] > row - cfg.sliding_window
            attn_mask = jnp.broadcast_to(causal[None], (B, T, T))
            out = self._xla_attention(q, k, v, attn_mask)

        out = nn.DenseGeneral(cfg.hidden, axis=(-2, -1), use_bias=False,
                              dtype=dt, name="o")(out)
        return out, new_cache

    def _use_flash(self) -> bool:
        cfg = self.cfg
        assert cfg.attn_impl in ("xla", "flash", "auto"), \
            f"attn_impl must be 'xla', 'flash' or 'auto', got {cfg.attn_impl!r}"
        impl = cfg.attn_impl
        if impl == "auto":
            # In-module fallback for DIRECT Decoder users (the factories
            # resolve 'auto' mesh-aware via _resolve_attn_impl first, so a
            # concrete impl arrives here). Mesh-blind, so be conservative:
            # flash only when the process can't even GSPMD-shard (1 device).
            impl = ("flash" if on_tpu() and jax.device_count() == 1
                    else "xla")
        # The fused kernel covers the standard path; softcapped / windowed /
        # rescaled layers (Gemma-2) take the materialized-scores path.
        return (impl == "flash" and cfg.attn_softcap == 0
                and cfg.query_scale == 0 and not self.local)

    def _xla_attention(self, q, k_all, v_all, attn_mask):
        """Materialized-scores path: [B,T,H,D] × [B,S,Hkv,D] → [B,T,H,D].
        Delegates to the one canonical einsum formulation so the XLA path,
        the flash VJP, and the parity oracle can never diverge."""
        from lazzaro_tpu.ops.flash_attention import reference_attention
        return reference_attention(q, k_all, v_all, attn_mask,
                                   scale=self.cfg.query_scale,
                                   softcap=self.cfg.attn_softcap)


class MLP(nn.Module):
    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        dt = jnp.dtype(self.cfg.dtype)
        gate = nn.Dense(self.cfg.mlp_dim, use_bias=False, dtype=dt, name="gate")(x)
        up = nn.Dense(self.cfg.mlp_dim, use_bias=False, dtype=dt, name="up")(x)
        h = nn.gelu(gate) * up
        return nn.Dense(self.cfg.hidden, use_bias=False, dtype=dt, name="down")(h)


class Block(nn.Module):
    cfg: LMConfig
    local: bool = False
    seq_mesh: Optional[Mesh] = None
    seq_axis: str = "sp"
    dp_axis: Optional[str] = "data"

    @nn.compact
    def __call__(self, x, positions, cache=None):
        h, new_cache = Attention(self.cfg, local=self.local,
                                 seq_mesh=self.seq_mesh,
                                 seq_axis=self.seq_axis,
                                 dp_axis=self.dp_axis, name="attn")(
            RMSNorm(name="ln1")(x), positions, cache)
        if self.cfg.post_norms:
            # Gemma-2 sandwich norms: normalize each sublayer OUTPUT before
            # the residual add (post_attention/post_feedforward_layernorm);
            # ln2 plays pre_feedforward_layernorm.
            x = x + RMSNorm(name="post_attn")(h)
            m = MLP(self.cfg, name="mlp")(RMSNorm(name="ln2")(x))
            x = x + RMSNorm(name="post_ffw")(m)
        else:
            x = x + h
            x = x + MLP(self.cfg, name="mlp")(RMSNorm(name="ln2")(x))
        return x, new_cache


class Decoder(nn.Module):
    cfg: LMConfig
    seq_mesh: Optional[Mesh] = None
    seq_axis: str = "sp"
    dp_axis: Optional[str] = "data"

    @nn.compact
    def __call__(self, tokens, positions, caches=None):
        """tokens [B, T] → logits [B, T, vocab]; caches: per-layer KV dicts."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.hidden))
        x = emb[tokens].astype(dt) * np.sqrt(cfg.hidden)
        new_caches = []
        for i in range(cfg.layers):
            cache_i = caches[i] if caches is not None else None
            # Gemma-2 alternation: EVEN layers slide, odd attend globally
            # (HF Gemma2: is_sliding = not bool(layer_idx % 2)).
            local = cfg.sliding_window > 0 and i % 2 == 0
            x, nc = Block(cfg, local=local, seq_mesh=self.seq_mesh,
                          seq_axis=self.seq_axis, dp_axis=self.dp_axis,
                          name=f"block_{i}")(x, positions, cache_i)
            new_caches.append(nc)
        x = RMSNorm(name="ln_f")(x)
        logits = (x.astype(jnp.float32) @ emb.T.astype(jnp.float32))
        if cfg.final_softcap > 0:
            logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
        return logits, (new_caches if caches is not None else None)


# ---------------------------------------------------------------------------
# Sharding rules: ('data', 'model') mesh
# ---------------------------------------------------------------------------


def param_specs(params: Dict, mesh: Optional[Mesh] = None) -> Dict:
    """PartitionSpec tree for pjit: embed sharded on vocab, attention on
    heads, MLP on the expanded axis; norms replicated. Dimensions not
    divisible by the mesh's 'model' axis fall back to replication (e.g. GQA
    kv_heads smaller than the tensor-parallel degree)."""
    model_size = mesh.shape["model"] if mesh is not None and "model" in mesh.axis_names else 1

    def fit(leaf, spec: P) -> P:
        """Drop the 'model' axis from the spec if that dim isn't divisible."""
        shape = getattr(leaf, "shape", ())
        for i, ax in enumerate(spec):
            if ax == "model" and (i >= len(shape) or shape[i] % max(model_size, 1)):
                return P()
        return spec

    def spec_for(path: Tuple[str, ...], leaf) -> P:
        name = "/".join(path)
        nd = getattr(leaf, "ndim", 0)
        if "embed" in name:
            return fit(leaf, P("model", None))
        if "attn" in name and any(k in name for k in ("q/", "k/", "v/")):
            return fit(leaf, P(None, "model", None) if nd == 3 else P(None, "model"))
        if "attn" in name and "o/" in name:
            return fit(leaf, P("model", None, None) if nd == 3 else P("model", None))
        if "mlp" in name and ("gate" in name or "up" in name):
            return fit(leaf, P(None, "model"))
        if "mlp" in name and "down" in name:
            return fit(leaf, P("model", None))
        return P()

    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def path_str(kp):
        return tuple(getattr(k, "key", str(k)) for k in kp)

    specs = {path_str(kp): spec_for(path_str(kp), leaf) for kp, leaf in flat}

    def rebuild(kp, leaf):
        return specs[path_str(kp)]

    return jax.tree_util.tree_map_with_path(rebuild, params)


def shard_params(params: Dict, mesh: Mesh) -> Dict:
    specs = param_specs(params, mesh)
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def _resolve_attn_impl(cfg: LMConfig, mesh: Optional[Mesh]) -> LMConfig:
    """attn_impl='flash' is a single-device kernel: pallas_call has no
    partitioning rule for a heads-sharded 'model' axis. Every place a config
    meets a mesh routes through here: 'auto' resolves to flash on single-
    device TPU and xla otherwise; an EXPLICIT 'flash' under tensor
    parallelism is a clear error instead of an obscure SPMD one."""
    import dataclasses
    # ANY multi-device mesh disqualifies the kernel — pallas_call has no
    # GSPMD partitioning rule, so a batch-sharded 'data' axis breaks it just
    # as surely as a heads-sharded 'model' axis.
    multi = mesh is not None and mesh.size > 1
    if cfg.attn_impl == "auto":
        impl = "flash" if on_tpu() and not multi else "xla"
        return dataclasses.replace(cfg, attn_impl=impl)
    if cfg.attn_impl == "flash" and multi:
        raise ValueError(
            "attn_impl='flash' is a single-device kernel; pallas_call has no "
            "GSPMD partitioning rule for sharded operands — use "
            "attn_impl='xla' (or the 'auto' default) under a >1-device mesh")
    return cfg


def _make_ce_train_step(model: Decoder, optimizer, tok_sharding=None):
    """Shared next-token-CE step body: one implementation, so the
    seq-parallel path can never diverge from the oracle it is tested
    against. ``tok_sharding`` (when given) constrains tokens AND mask."""

    def loss_fn(params, tokens, mask):
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        logits, _ = model.apply({"params": params}, tokens, positions)
        targets = tokens[:, 1:]
        logits = logits[:, :-1]
        mask = mask[:, 1:].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    def train_step(params, opt_state, tokens, mask):
        if tok_sharding is not None:
            tokens = jax.lax.with_sharding_constraint(tokens, tok_sharding)
            mask = jax.lax.with_sharding_constraint(mask, tok_sharding)
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1))


def make_train_step(cfg: LMConfig, optimizer, mesh: Optional[Mesh] = None):
    """Next-token CE train step. With a mesh: batch over 'data', params over
    'model' (call ``shard_params`` on params and optimizer state first).
    attn_impl='flash' (the single-device-TPU 'auto' resolution) fuses
    BOTH directions: the VJP recomputes scores blockwise from the stored
    log-sum-exp, so training peak HBM is O(T·D) — measured 101 MB vs
    8.7 GB for materialized scores at T=8192 (ops/flash_attention.py)."""
    cfg = _resolve_attn_impl(cfg, mesh)
    sharding = (NamedSharding(mesh, P("data", None))
                if mesh is not None else None)
    return _make_ce_train_step(Decoder(cfg), optimizer, sharding)


def make_seq_parallel_train_step(cfg: LMConfig, optimizer, mesh: Mesh,
                                 seq_axis: str = "sp",
                                 dp_axis: Optional[str] = "data"):
    """Long-context train step: activations sharded along TIME over
    ``seq_axis`` (ring attention via ppermute — per-chip attention memory is
    O(T/n·d)), composed with batch data-parallelism over ``dp_axis``. This is
    how sequences far beyond one chip's HBM train: the [B, T] token block is
    laid out (dp, sp) over the mesh, every elementwise/matmul op partitions
    along T for free under GSPMD, and only attention pays ring hops on ICI.

    Gemma-2 softcap/sliding-window/rescaled attention is not expressible on
    the ring kernel yet — rejected explicitly rather than silently wrong."""
    if cfg.attn_softcap or cfg.sliding_window or cfg.query_scale:
        raise ValueError(
            "sequence-parallel training supports standard scaled-dot-product "
            "attention only (no softcap/sliding-window/query_scale)")
    if dp_axis is not None and dp_axis not in mesh.axis_names:
        dp_axis = None
    model = Decoder(cfg, seq_mesh=mesh, seq_axis=seq_axis, dp_axis=dp_axis)
    return _make_ce_train_step(model, optimizer,
                               NamedSharding(mesh, P(dp_axis, seq_axis)))


# ---------------------------------------------------------------------------
# Host wrapper: init / generate / checkpoint
# ---------------------------------------------------------------------------


class LanguageModel:
    def __init__(self, cfg: Optional[LMConfig] = None, seed: int = 0,
                 mesh: Optional[Mesh] = None, tokenizer=None,
                 init_params: bool = True):
        self.cfg = cfg or LMConfig.small()
        self.cfg = _resolve_attn_impl(self.cfg, mesh)
        self.tokenizer = tokenizer if tokenizer is not None else ByteTokenizer()
        eos = getattr(self.tokenizer, "EOS", None)      # explicit None checks:
        if eos is None:                                 # an EOS of id 0 is valid
            eos = getattr(self.tokenizer, "eos_id", None)
        self.eos_id = int(eos) if eos is not None else ByteTokenizer.EOS
        self.model = Decoder(self.cfg)
        if init_params:
            dummy = jnp.zeros((1, 8), jnp.int32)
            pos = jnp.zeros((1, 8), jnp.int32)
            variables = self.model.init(jax.random.PRNGKey(seed), dummy, pos)
            self.params = variables["params"]
            if mesh is not None:
                self.params = shard_params(self.params, mesh)
        else:
            self.params = None            # caller installs params (from_hf)
        self.mesh = mesh
        self._prefill = jax.jit(self._prefill_impl)
        self._decode_one = jax.jit(self._decode_impl)
        self._json_loops: dict = {}       # max_new -> jitted device loop

    @classmethod
    def from_hf(cls, hf_model, hf_tokenizer=None,
                max_seq: int = 2048, dtype: str = "float32",
                mesh: Optional[Mesh] = None) -> "LanguageModel":
        """Build from a local ``transformers`` Gemma-family causal LM — the
        decoder-side analog of ``TextEncoder.from_hf`` (zero egress; the
        checkpoint must already be on disk/in memory). Maps GemmaModel
        weights onto the in-tree Decoder: torch Linear kernels transposed
        and reshaped to (hidden, heads, head_dim), RMSNorm weights shifted
        by +1 (Gemma computes ``x * (1 + w)``; this module multiplies by the
        scale directly), embeddings tied for the LM head.

        ``hf_tokenizer``: optional transformers tokenizer wrapped via
        ``HFLMTokenizerAdapter`` — without it the byte tokenizer is kept
        (mechanically fine, but ids won't match the checkpoint's
        sentencepiece vocab, so generations are meaningless)."""
        hc = hf_model.config
        model_type = getattr(hc, "model_type", "gemma")
        if model_type not in ("gemma", "gemma2"):
            raise ValueError(
                f"from_hf supports Gemma-1/Gemma-2-family checkpoints "
                f"(model_type 'gemma'/'gemma2'), got {model_type!r}")
        # Numerics this module hardcodes — reject configs that differ rather
        # than silently produce wrong logits.
        if getattr(hc, "attention_bias", False):
            raise ValueError("attention_bias=True checkpoints unsupported "
                             "(in-tree attention projections have no bias)")
        eps = float(getattr(hc, "rms_norm_eps", 1e-6))
        if abs(eps - 1e-6) > 1e-12:
            raise ValueError(f"rms_norm_eps {eps} != the hardcoded 1e-6")
        act = (getattr(hc, "hidden_activation", None)
               or getattr(hc, "hidden_act", None))
        if act not in (None, "gelu_pytorch_tanh"):
            raise ValueError(f"hidden activation {act!r} != the in-tree "
                             f"tanh-approximate GeLU ('gelu_pytorch_tanh')")
        g2 = {}
        if model_type == "gemma2":
            # softcapping + sandwich norms + alternating local/global
            # attention + query_pre_attn_scalar scaling
            g2 = dict(
                attn_softcap=float(hc.attn_logit_softcapping or 0.0),
                final_softcap=float(hc.final_logit_softcapping or 0.0),
                sliding_window=int(hc.sliding_window or 0),
                query_scale=float(hc.query_pre_attn_scalar) ** -0.5,
                post_norms=True)
        cfg = LMConfig(
            vocab_size=hc.vocab_size, hidden=hc.hidden_size,
            layers=hc.num_hidden_layers, heads=hc.num_attention_heads,
            kv_heads=hc.num_key_value_heads, head_dim=hc.head_dim,
            mlp_dim=hc.intermediate_size,
            max_seq=min(max_seq, hc.max_position_embeddings),
            rope_theta=float(getattr(hc, "rope_theta", 10000.0)),
            dtype=dtype, **g2)
        tok = HFLMTokenizerAdapter(hf_tokenizer) if hf_tokenizer is not None else None
        lm = cls(cfg, tokenizer=tok, mesh=mesh, init_params=False)
        params = gemma_params_from_hf(hf_model, cfg)
        lm.params = shard_params(params, mesh) if mesh is not None else params
        return lm

    # -- checkpointing ------------------------------------------------------
    def save_params(self, ckpt_dir: str) -> None:
        import orbax.checkpoint as ocp
        ocp.StandardCheckpointer().save(ckpt_dir, self.params)

    def load_params(self, ckpt_dir: str) -> None:
        import orbax.checkpoint as ocp
        self.params = ocp.StandardCheckpointer().restore(ckpt_dir, self.params)

    # -- inference ----------------------------------------------------------
    def _empty_cache(self, batch: int):
        dt = jnp.dtype(self.cfg.dtype)
        return [{"k": jnp.zeros((batch, self.cfg.max_seq, self.cfg.kv_heads,
                                 self.cfg.head_dim), dt),
                 "v": jnp.zeros((batch, self.cfg.max_seq, self.cfg.kv_heads,
                                 self.cfg.head_dim), dt)}
                for _ in range(self.cfg.layers)]

    def _prefill_impl(self, params, tokens, positions, caches):
        logits, caches = self.model.apply({"params": params}, tokens, positions,
                                          caches)
        return logits[:, -1], caches

    def _decode_impl(self, params, token, position, caches):
        logits, caches = self.model.apply(
            {"params": params}, token[:, None], position[:, None], caches)
        return logits[:, -1], caches

    def _prep_prompt(self, prompt: str, max_new_tokens: int,
                     extra_ids: tuple = ()):
        """Shared generation preamble: clamp the budget, keep the prompt tail
        that fits (a naive negative slice turns into [-0:] when the budget
        hits zero and silently keeps everything), prefill the KV cache.
        ``extra_ids`` are teacher-forced tokens appended AFTER the prompt —
        they ride the same prefill (one dispatch), not per-token decode
        steps; generate_json uses this for scaffold prefixes.
        Returns (clamped_max_new_tokens, last-position logits, caches, pos)."""
        cfg = self.cfg
        # extra_ids consume context exactly like generated tokens: clamp the
        # budget net of them, or a long scaffold could push prompt_budget
        # negative (silently dropping the whole prompt) or overflow the KV
        # cache outright.
        max_new_tokens = min(max_new_tokens, cfg.max_seq - 2 - len(extra_ids))
        if max_new_tokens < 1:
            raise ValueError(
                f"{len(extra_ids)} forced prefix tokens leave no generation "
                f"budget in max_seq={cfg.max_seq}")
        prompt_budget = cfg.max_seq - 1 - max_new_tokens - len(extra_ids)
        ids = self.tokenizer.encode(prompt)
        if len(ids) > prompt_budget:
            ids = ids[len(ids) - prompt_budget:]
        ids = list(ids) + list(extra_ids)
        tokens = jnp.asarray([ids], jnp.int32)
        positions = jnp.arange(len(ids))[None, :]
        caches = self._empty_cache(1)
        logits, caches = self._prefill(self.params, tokens, positions, caches)
        return max_new_tokens, logits, caches, len(ids)

    def _token_stream(self, prompt: str, max_new_tokens: int,
                      temperature: float, seed: int):
        """The ONE sampling loop: prefill, then sample → yield id → decode
        step, stopping on EOS or the context limit. Both generate() and
        generate_stream() consume this, so they can never drift."""
        cfg = self.cfg
        max_new_tokens, logits, caches, pos = self._prep_prompt(
            prompt, max_new_tokens)
        key = jax.random.PRNGKey(seed)
        for _ in range(max_new_tokens):
            if temperature > 0:
                key, sub = jax.random.split(key)
                token = jax.random.categorical(sub, logits / temperature, axis=-1)
            else:
                token = jnp.argmax(logits, axis=-1)
            tid = int(token[0])
            if tid == self.eos_id or pos >= cfg.max_seq - 1:
                return
            yield tid
            logits, caches = self._decode_one(
                self.params, jnp.asarray([tid], jnp.int32),
                jnp.asarray([pos], jnp.int32), caches)
            pos += 1

    def generate(self, prompt: str, max_new_tokens: int = 64,
                 temperature: float = 0.0, seed: int = 0) -> str:
        ids = list(self._token_stream(prompt, max_new_tokens, temperature, seed))
        return self.tokenizer.decode(ids)

    def generate_stream(self, prompt: str, max_new_tokens: int = 64,
                        temperature: float = 0.0, seed: int = 0):
        """Incremental generation: yields text pieces as tokens decode;
        the concatenated pieces equal ``generate()``'s output exactly.

        Byte tokenizer: an incremental UTF-8 decoder buffers partial
        multi-byte sequences and replaces invalid ones just like
        ``bytes.decode(errors="replace")``. Subword tokenizers: the growing
        prefix is re-decoded and the text delta yielded (per-token decode
        would drop sentencepiece's leading-space markers)."""
        import codecs

        stream = self._token_stream(prompt, max_new_tokens, temperature, seed)
        if isinstance(self.tokenizer, ByteTokenizer):
            decoder = codecs.getincrementaldecoder("utf-8")("replace")
            for tid in stream:
                if 0 <= tid < 256:
                    piece = decoder.decode(bytes([tid]))
                    if piece:
                        yield piece
            tail = decoder.decode(b"", final=True)
            if tail:
                yield tail
        else:
            ids: list = []
            prev = ""
            for tid in stream:
                ids.append(tid)
                text = self.tokenizer.decode(ids)
                if len(text) > len(prev) and text.startswith(prev):
                    yield text[len(prev):]
                    prev = text
            # Tokens held back by a non-monotone decode land here.
            final = self.tokenizer.decode(ids) if ids else ""
            if len(final) > len(prev) and final.startswith(prev):
                yield final[len(prev):]

    def generate_json(self, prompt: str, max_new_tokens: int = 256,
                      temperature: float = 0.0, seed: int = 0,
                      force_object: bool = True,
                      scaffold: Optional[str] = None,
                      device_loop: bool = True) -> str:
        """Grammar-constrained generation: the output is valid JSON by
        construction (any weights, including random). A byte-level pushdown
        automaton (``models/json_constrain.py``) computes the legal next-byte
        set each step; illegal logits are masked to -inf before sampling; if
        the token budget runs out mid-document, the shortest closing suffix
        completes it. Replaces the reference's trust-the-API
        ``response_format`` + fence-stripping + parse-failure path
        (providers.py:10-19, memory_system.py:684-703).

        ``scaffold``: a literal JSON prefix the output MUST start with (e.g.
        ``'{"memories": [{"content": "'``) — teacher-forced through the
        prefill in one dispatch, validated byte-by-byte against the grammar
        automaton, then generation continues from the automaton state the
        scaffold reached. This is schema-shaped decoding: callers pin the
        keys/structure they need and let the model fill the values.

        ``device_loop=True`` (default) runs the entire constrained decode
        inside ``lax.while_loop`` with the automaton state on device
        (models/json_device.py) — one dispatch + one readback total.
        ``device_loop=False`` keeps the per-byte host loop (debugging /
        oracle for parity tests). Greedy outputs are identical; sampled
        outputs differ only in PRNG stream shape."""
        from lazzaro_tpu.models.json_constrain import JsonState, constrain_mask

        if not isinstance(self.tokenizer, ByteTokenizer):
            raise ValueError(
                "generate_json requires the byte tokenizer (the JSON grammar "
                "automaton masks logits per BYTE; subword ids don't map 1:1)")
        cfg = self.cfg
        state = JsonState(force_object=force_object)
        out = bytearray()
        scaffold_ids: tuple = ()
        if scaffold:
            sbytes = scaffold.encode("utf-8")
            for i, b in enumerate(sbytes):
                mask = constrain_mask(state, cfg.vocab_size, ByteTokenizer.EOS)
                if not mask[b]:
                    raise ValueError(
                        f"scaffold is not a valid JSON prefix at byte {i} "
                        f"({bytes([b])!r} after {sbytes[:i]!r})")
                out.append(b)
                state.feed(b)
            scaffold_ids = tuple(int(b) for b in sbytes)
        max_new_tokens, logits, caches, pos = self._prep_prompt(
            prompt, max_new_tokens, extra_ids=scaffold_ids)

        if device_loop:
            # The whole sample→mask→feed→decode loop runs ON DEVICE
            # (models/json_device.py): one dispatch + one readback for the
            # entire generation, vs one host round trip PER BYTE.
            from lazzaro_tpu.models import json_device as JD

            dstate = JD.encode_host_state(state)
            run = self._json_loop(max_new_tokens)
            out_ids, _n = run(self.params, logits, caches, jnp.int32(pos),
                              dstate, jnp.float32(temperature),
                              jax.random.PRNGKey(seed))
            for tid in np.asarray(out_ids).tolist():
                if tid < 0:
                    break
                out.append(tid)
                state.feed(tid)          # host replay → closing_suffix state
        else:
            key = jax.random.PRNGKey(seed)
            for _ in range(max_new_tokens):
                mask = constrain_mask(state, cfg.vocab_size, ByteTokenizer.EOS)
                host_logits = np.array(logits[0], np.float32)  # writable copy
                host_logits[~mask] = -np.inf
                if temperature > 0:
                    key, sub = jax.random.split(key)
                    tid = int(jax.random.categorical(
                        sub, jnp.asarray(host_logits)[None, :] / temperature,
                        axis=-1)[0])
                else:
                    tid = int(host_logits.argmax())
                if tid == ByteTokenizer.EOS:
                    break
                out.append(tid)
                state.feed(tid)
                if state.mode == "done":
                    # Structurally complete (container closed / literal /
                    # string ended) — only whitespace could follow. A
                    # top-level number is `done` but extendable ("4" → "42"),
                    # so it keeps decoding until the model itself picks EOS
                    # (legal once done).
                    break
                if pos >= cfg.max_seq - 1:
                    break
                logits, caches = self._decode_one(
                    self.params, jnp.asarray([tid], jnp.int32),
                    jnp.asarray([pos], jnp.int32), caches)
                pos += 1
        out += state.closing_suffix()
        return out.decode("utf-8", errors="replace")

    def _json_loop(self, max_new: int):
        """Build (and cache per token budget) the jitted on-device
        constrained-decode loop: ``lax.while_loop`` carrying the KV caches,
        the JSON automaton state, and the output byte buffer. Greedy when
        temperature == 0, else categorical over the masked logits."""
        if max_new in self._json_loops:
            return self._json_loops[max_new]
        from lazzaro_tpu.models import json_device as JD

        vocab = self.cfg.vocab_size
        eos = ByteTokenizer.EOS
        decode = self._decode_impl

        @jax.jit
        def run(params, logits0, caches0, pos0, dstate0, temperature, key):
            out0 = jnp.full((max_new,), -1, jnp.int32)

            def cond(carry):
                t, done = carry[0], carry[1]
                return (~done) & (t < max_new)

            def body(carry):
                t, _, logits, caches, pos, st, out_buf, k = carry
                mask = JD.allowed_mask(st, vocab, eos)
                ml = jnp.where(mask, logits[0].astype(jnp.float32), -jnp.inf)
                k, sub = jax.random.split(k)
                tid = jnp.where(
                    temperature > 0,
                    jax.random.categorical(
                        sub, ml / jnp.maximum(temperature, 1e-6)),
                    jnp.argmax(ml)).astype(jnp.int32)
                is_eos = tid == eos
                out_buf = out_buf.at[t].set(jnp.where(is_eos, -1, tid))
                fed = JD.feed(st, jnp.clip(tid, 0, 255))
                st2 = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(is_eos, a, b), st, fed)
                done2 = is_eos | (st2.mode == JD.DONE)
                # skip the transformer step once the document is complete —
                # its logits would be discarded on loop exit (a short
                # extraction would otherwise waste a full decode's FLOPs)
                logits2, caches2 = jax.lax.cond(
                    done2,
                    lambda p, i, q, c: (logits, c),
                    decode, params, tid[None], pos[None], caches)
                return (t + 1, done2, logits2, caches2, pos + 1, st2,
                        out_buf, k)

            carry = (jnp.int32(0), jnp.bool_(False), logits0, caches0,
                     jnp.int32(pos0), dstate0, out0, key)
            t, _, _, _, _, _, out_buf, _ = jax.lax.while_loop(cond, body, carry)
            return out_buf, t

        self._json_loops[max_new] = run
        return run

    def logits_for(self, text: str) -> np.ndarray:
        """Full-sequence forward (no cache) — training/eval path."""
        ids = self.tokenizer.encode(text)
        tokens = jnp.asarray([ids], jnp.int32)
        positions = jnp.arange(len(ids))[None, :]
        logits, _ = self.model.apply({"params": self.params}, tokens, positions)
        return np.asarray(logits[0])


class HFLMTokenizerAdapter:
    """Duck-types the ByteTokenizer surface over a HuggingFace tokenizer so
    a real checkpoint's (e.g. sentencepiece) vocab can drive generation."""

    def __init__(self, hf_tokenizer):
        self.hf = hf_tokenizer

    @property
    def eos_id(self) -> int:
        eos = getattr(self.hf, "eos_token_id", None)
        return int(eos) if eos is not None else -1

    def encode(self, text: str, add_bos: bool = True,
               add_eos: bool = False) -> list:
        ids = self.hf.encode(text, add_special_tokens=False)
        bos = getattr(self.hf, "bos_token_id", None)
        if add_bos and bos is not None:
            ids = [int(bos)] + list(ids)
        if add_eos and self.eos_id >= 0:
            ids = list(ids) + [self.eos_id]
        return list(ids)

    def decode(self, ids) -> str:
        return self.hf.decode([int(i) for i in ids],
                              skip_special_tokens=True)


def gemma_params_from_hf(hf_model, cfg: LMConfig) -> Dict:
    """Map a torch ``transformers`` Gemma-family causal LM's state_dict onto
    ``Decoder`` params. Conventions handled: torch Linear kernels are
    [out, in] → transposed (and reshaped to (hidden, heads, head_dim) for
    q/k/v, (heads, head_dim, hidden) for o); Gemma RMSNorm multiplies by
    ``1 + weight`` → +1 folded into the scale; embeddings are tied for the
    LM head (``Decoder`` computes logits against the embedding table)."""
    # .float() first: Gemma checkpoints are natively bf16 and torch bf16
    # tensors do not support .numpy().
    sd = {k: np.asarray(v.detach().cpu().float().numpy())
          for k, v in hf_model.state_dict().items()}
    pre = "model." if any(k.startswith("model.") for k in sd) else ""

    def ln(name):
        return {"scale": sd[name] + 1.0}

    params: Dict = {
        "embed": sd[f"{pre}embed_tokens.weight"],
        "ln_f": ln(f"{pre}norm.weight"),
    }
    H, Hkv, D, hid = cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.hidden
    for i in range(cfg.layers):
        a = f"{pre}layers.{i}"
        if cfg.post_norms:
            # Gemma-2 sandwich norms: HF's post_attention_layernorm is the
            # attn-OUTPUT norm; pre_feedforward_layernorm is the pre-MLP one
            # (in Gemma-1, post_attention_layernorm plays the pre-MLP role).
            norms = {
                "ln1": ln(f"{a}.input_layernorm.weight"),
                "post_attn": ln(f"{a}.post_attention_layernorm.weight"),
                "ln2": ln(f"{a}.pre_feedforward_layernorm.weight"),
                "post_ffw": ln(f"{a}.post_feedforward_layernorm.weight"),
            }
        else:
            norms = {
                "ln1": ln(f"{a}.input_layernorm.weight"),
                "ln2": ln(f"{a}.post_attention_layernorm.weight"),
            }
        params[f"block_{i}"] = {
            **norms,
            "attn": {
                "q": {"kernel": sd[f"{a}.self_attn.q_proj.weight"].T
                      .reshape(hid, H, D)},
                "k": {"kernel": sd[f"{a}.self_attn.k_proj.weight"].T
                      .reshape(hid, Hkv, D)},
                "v": {"kernel": sd[f"{a}.self_attn.v_proj.weight"].T
                      .reshape(hid, Hkv, D)},
                "o": {"kernel": sd[f"{a}.self_attn.o_proj.weight"].T
                      .reshape(H, D, hid)},
            },
            "mlp": {
                "gate": {"kernel": sd[f"{a}.mlp.gate_proj.weight"].T},
                "up": {"kernel": sd[f"{a}.mlp.up_proj.weight"].T},
                "down": {"kernel": sd[f"{a}.mlp.down_proj.weight"].T},
            },
        }
    return jax.tree_util.tree_map(jnp.asarray, params)
