"""Transformer core (flax.linen).

Re-creates the exact math of the reference transformer
(``/root/reference/transformer.py``) with its three behavioural quirks, which
are load-bearing for loss-curve parity (SURVEY.md §7.5):

* **Q1 — non-standard head geometry.** K/Q/V projections map ``emb →
  emb*heads`` so *every head has the full emb dimension* (reference
  ``transformer.py:34-36,52-59``), and attention logits are scaled by dividing
  both queries and keys by ``emb ** (1/4)`` (``transformer.py:62-63``).
  ``standard_heads=True`` switches to conventional ``emb//heads`` heads for the
  performance configs.
* **Q2 — post-LN residuals**, residual adds the *query* input, dropout after
  each sub-layer: ``x = norm1(attended + q); x = do(x); x = norm2(ff(x) + x);
  x = do(x)`` (``transformer.py:120-140``).
* **Key threading.** Blocks pass ``(q, k, mask)`` tuples and return the
  *original* ``k`` unchanged (``transformer.py:126,140``), so with ``depth>1``
  every block attends its evolving queries against the **layer-0 key
  embeddings** — not the previous block's output. Preserved exactly.

Everything is expressed as batched einsums so XLA tiles the contractions onto
the MXU; there are no data-dependent shapes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

NEG_MASK_VALUE = -1e9  # reference masked_fill value (transformer.py:73)


def orthogonal_or_default(use_orthogonal: bool, scale: float = 2 ** 0.5):
    """Kernel init selector: reference optionally applies ``orthogonal_init_``
    module-wise (``/root/reference/n_transf_mixer.py:48-50``, M12)."""
    if use_orthogonal:
        return nn.initializers.orthogonal(scale)
    return nn.initializers.lecun_normal()


class MultiHeadAttention(nn.Module):
    """Multi-head attention with the reference's full-emb head geometry (Q1).

    Reference: ``/root/reference/transformer.py:20-84``.
    """

    emb: int
    heads: int = 8
    causal: bool = False          # reference ``mask`` ctor flag (upper-tri fill)
    standard_heads: bool = False  # perf mode: per-head dim = emb // heads
    use_orthogonal: bool = False
    dtype: jnp.dtype = jnp.float32   # compute dtype (bf16 = MXU-native perf mode)
    # attention kernel (config kernels.attention, docs/PERF.md): "xla" =
    # the einsum→softmax→einsum path below (default; materializes the
    # (b, h, t_q, t_k) logits tensor); "pallas" = the fused flash-style
    # kernel (kernels/attention.py — tiled online softmax, f32
    # accumulators, logits live only in VMEM). Parity pinned by
    # tests/test_kernels.py; interpret mode makes pallas CPU-testable.
    attn_impl: str = "xla"

    @nn.compact
    def __call__(self, q: jax.Array, k: jax.Array,
                 mask: Optional[jax.Array] = None) -> jax.Array:
        b, t_q, e_q = q.shape
        _, t_k, e = k.shape
        assert e == e_q == self.emb, (e, e_q, self.emb)
        assert self.attn_impl in ("xla", "pallas"), self.attn_impl
        h = self.heads
        if self.standard_heads:
            assert self.emb % h == 0
            head_dim = self.emb // h
        else:
            head_dim = self.emb  # Q1: full-width heads

        dense = lambda name: nn.Dense(
            h * head_dim, use_bias=False, name=name, dtype=self.dtype,
            kernel_init=orthogonal_or_default(self.use_orthogonal))
        keys = dense("tokeys")(k).reshape(b, t_k, h, head_dim)
        queries = dense("toqueries")(q).reshape(b, t_q, h, head_dim)
        values = dense("tovalues")(k).reshape(b, t_k, h, head_dim)

        # Q1: scale queries AND keys by head_dim**(1/4) (transformer.py:62-63)
        scale = head_dim ** -0.25
        queries = queries * scale
        keys = keys * scale

        if mask is not None:
            # padding mask: 0 entries are suppressed (transformer.py:72-73).
            # Accepts (b, t_q, t_k) — broadcast over heads — or (b, h/1, t_q, t_k).
            if mask.ndim == 3:
                mask = mask[:, None, :, :]
            assert mask.ndim == 4, f"mask must be 3D or 4D, got {mask.shape}"

        if self.attn_impl == "pallas":
            # fused flash kernel: tiled QK^T → masked online softmax →
            # PV, f32 accumulators, never materializing the logits
            # tensor (kernels/attention.py). Same mask/causal semantics
            # as below; softmax statistics are f32 in BOTH dtypes (the
            # bf16 path is better-conditioned than the einsum one).
            from ..kernels.attention import flash_attention
            out = flash_attention(
                jnp.swapaxes(queries, 1, 2), jnp.swapaxes(keys, 1, 2),
                jnp.swapaxes(values, 1, 2), mask=mask, causal=self.causal)
            out = jnp.swapaxes(out, 1, 2).reshape(b, t_q, h * head_dim)
            return nn.Dense(self.emb, name="unifyheads", dtype=self.dtype,
                            kernel_init=orthogonal_or_default(
                                self.use_orthogonal))(out)

        logits = jnp.einsum("bqhd,bkhd->bhqk", queries, keys)

        if self.causal:
            # reference mask_ fills the upper triangle excluding the diagonal
            # with -inf when used from attention (transformer.py:69-70)
            tri = jnp.triu(jnp.ones((t_q, t_k), dtype=bool), k=1)
            logits = jnp.where(tri[None, None], -jnp.inf, logits)
        if mask is not None:
            logits = jnp.where(mask == 0, NEG_MASK_VALUE, logits)

        # parity mode (f32) keeps f32 softmax; bf16 perf mode stays in bf16
        # end-to-end — bf16 shares f32's exponent range, so max-subtracted
        # softmax is range-safe, and skipping the cast avoids materializing
        # the (b, h, t, t) logits twice
        if self.dtype == jnp.float32:
            attn = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        else:
            attn = jax.nn.softmax(logits, axis=-1)
        attn = attn.astype(values.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", attn, values)
        out = out.reshape(b, t_q, h * head_dim)
        return nn.Dense(self.emb, name="unifyheads", dtype=self.dtype,
                        kernel_init=orthogonal_or_default(self.use_orthogonal))(out)


class TransformerBlock(nn.Module):
    """Post-LN transformer block (Q2). Reference ``transformer.py:87-140``."""

    emb: int
    heads: int
    causal: bool = False
    ff_hidden_mult: int = 4
    dropout: float = 0.0
    standard_heads: bool = False
    use_orthogonal: bool = False
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "xla"        # kernels.attention switch (see MHA)

    @nn.compact
    def __call__(self, q: jax.Array, k: jax.Array,
                 mask: Optional[jax.Array] = None,
                 deterministic: bool = True) -> jax.Array:
        with jax.named_scope("agent.attention"):
            attended = MultiHeadAttention(
                emb=self.emb, heads=self.heads, causal=self.causal,
                standard_heads=self.standard_heads,
                use_orthogonal=self.use_orthogonal, dtype=self.dtype,
                attn_impl=self.attn_impl,
                name="attention")(q, k, mask)

        # the block's tail (post-LN residuals + FFN) is one scope, as in
        # ops/query_slice._block_tail
        with jax.named_scope("agent.ff"):
            x = nn.LayerNorm(name="norm1", dtype=self.dtype)(attended + q)
            x = nn.Dropout(self.dropout, deterministic=deterministic)(x)

            init = orthogonal_or_default(self.use_orthogonal)
            ff = nn.Dense(self.ff_hidden_mult * self.emb, name="ff1",
                          dtype=self.dtype, kernel_init=init)(x)
            ff = nn.relu(ff)
            ff = nn.Dense(self.emb, name="ff2", dtype=self.dtype,
                          kernel_init=init)(ff)

            x = nn.LayerNorm(name="norm2", dtype=self.dtype)(ff + x)
            x = nn.Dropout(self.dropout, deterministic=deterministic)(x)
            return x


class Transformer(nn.Module):
    """Stack of ``depth`` non-causal blocks returning final queries.

    Reference ``transformer.py:143-178``. Keys stay pinned to the layer-0
    input across blocks (see module docstring).
    """

    emb: int
    heads: int
    depth: int
    ff_hidden_mult: int = 4
    dropout: float = 0.0
    standard_heads: bool = False
    use_orthogonal: bool = False
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "xla"        # kernels.attention switch (see MHA)

    @nn.compact
    def __call__(self, q: jax.Array, k: jax.Array,
                 mask: Optional[jax.Array] = None,
                 deterministic: bool = True) -> jax.Array:
        x = q
        for i in range(self.depth):
            x = TransformerBlock(
                emb=self.emb, heads=self.heads, causal=False,
                ff_hidden_mult=self.ff_hidden_mult, dropout=self.dropout,
                standard_heads=self.standard_heads,
                use_orthogonal=self.use_orthogonal, dtype=self.dtype,
                attn_impl=self.attn_impl,
                name=f"block_{i}")(x, k, mask, deterministic=deterministic)
        return x
