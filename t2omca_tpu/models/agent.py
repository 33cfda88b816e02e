"""Transformer Q-agent.

Re-creates ``TransformerAgent`` (``/root/reference/transf_agent.py:8-76``):
entity-tokenized observations are linearly embedded, the recurrent hidden
state is **prepended as token 0**, the stack self-attends (q = k = tokens),
token 0 becomes the new hidden state and is projected to per-action Q-values.
Recurrence without an RNN — the hidden token is the memory (TransfQMIX).

Shapes: inputs ``(batch, n_agents, obs)`` are folded to
``(batch*n_agents, n_entities, feat)`` exactly as the reference does
(``transf_agent.py:56-59``), so all agents share parameters and one big MXU
matmul serves the whole batch×agent axis.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .noisy import NoisyLinear
from .transformer import Transformer, orthogonal_or_default


class TransformerAgent(nn.Module):
    n_agents: int
    n_entities: int          # reference: n_entities_obs override, else n_entities
    feat_dim: int            # obs_entity_feats
    emb: int
    heads: int
    depth: int
    n_actions: int
    ff_hidden_mult: int = 4
    dropout: float = 0.0
    noisy: bool = False      # action_selector == "noisy-new" (transf_agent.py:37-39)
    standard_heads: bool = False
    use_orthogonal: bool = False
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "xla"   # kernels.attention switch (models/transformer.py)

    @nn.compact
    def __call__(self, inputs: jax.Array, hidden_state: jax.Array,
                 deterministic: bool = True) -> Tuple[jax.Array, jax.Array]:
        b, a, _ = inputs.shape
        with jax.named_scope("agent.embed"):
            x = inputs.reshape(b * a, self.n_entities, self.feat_dim)
            h = hidden_state.reshape(b * a, 1, self.emb).astype(self.dtype)

            embs = nn.Dense(
                self.emb, name="feat_embedding", dtype=self.dtype,
                kernel_init=orthogonal_or_default(self.use_orthogonal))(x)

            # hidden token prepended at position 0 (transf_agent.py:65)
            tokens = jnp.concatenate([h, embs], axis=1)

        out = Transformer(
            emb=self.emb, heads=self.heads, depth=self.depth,
            ff_hidden_mult=self.ff_hidden_mult, dropout=self.dropout,
            standard_heads=self.standard_heads,
            use_orthogonal=self.use_orthogonal, dtype=self.dtype,
            attn_impl=self.attn_impl,
            name="transformer")(tokens, tokens, deterministic=deterministic)

        with jax.named_scope("agent.head"):
            # token 0 = new hidden (:71)
            h_new = out[:, 0:1, :].astype(jnp.float32)

            if self.noisy:
                q = NoisyLinear(self.n_actions, name="q_basic")(
                    h_new, deterministic=deterministic)
            else:
                init = orthogonal_or_default(self.use_orthogonal)
                q = nn.Dense(self.n_actions, name="q_basic",
                             kernel_init=init)(h_new)

            # Q-values and the carried hidden token stay f32 regardless of
            # the compute dtype (selector argmax + TD math need full
            # precision)
            return (q.astype(jnp.float32).reshape(b, a, self.n_actions),
                    h_new.reshape(b, a, self.emb))

    def initial_hidden(self, batch_size: int) -> jax.Array:
        """Zeros ``(batch, n_agents, emb)`` (reference ``init_hidden`` zeros
        ``(1, emb)`` broadcast by the MAC, ``transf_agent.py:50-52``)."""
        return jnp.zeros((batch_size, self.n_agents, self.emb))
