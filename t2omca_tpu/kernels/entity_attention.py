"""One block's entity-table attention as one VMEM-resident Pallas kernel.

Acting's head-width entity forward (``ops/query_slice.py``,
``_entity_attention_heads``: ``head_dim < emb``) is, per env, a handful
of small products — queries against two ``(A, H·D)`` entity tables and
the own hidden token, a masked softmax, the context — between four
``(B·A, E) × (E, H·D)`` projections. As plain XLA every one of them is
an operation of its own whose result goes to HBM and comes back: about
forty operations and ≈ 1 GB of traffic a block at the north-star shapes
(PERF.md §5). This kernel computes everything ``_entity_attention_heads``
returns for a TILE of envs a grid step, with the tile's working set held
in VMEM from the projections to the ``wu`` product; the only thing
written to HBM is ``attended``.

Layout. ``H·D`` stays in the lanes and heads are taken in groups of
``g = 128 // head_dim`` (a group's ``(h, a)`` query rows, each masked to
its own head's lanes, against a group's 128 lanes), and the logits are
laid out with the KEYS in the rows, so that the softmax reduces over
rows and not over lanes — both as PR 26 found for this chip, and both
still true inside a kernel (keys in the lanes, as a flash kernel has
them, measured 0.70 ms a block against 0.56). Per env and group the
logits are two ``(2A, g·A)`` float32 tiles:

* tile 1 — the ``2A`` entity-table rows (visible then masked);
* tile 2 — rows ``[0, A)``: the is-self key ``wek[8]`` (every row the
  same numbers: the diagonal correction of tile 1 before its
  ``1 / std``), rows ``[A, 2A)``: the agents' own hidden tokens
  (key 0), of which a query keeps its own.

Key 0 and the is-self value ride the same two products as the tables
(their probabilities are rows of the second tile, their values rows of
its value operand), so no per-query scalar ever changes layout; the
context is ``pᵀ · v``, the MXU's transposed-left product.

Visibility arrives as an additive plane ``mask (B·2A, 128)`` — ``-1e30``
in lane ``a`` where agent ``a`` does not see the table row, zero past
lane ``A`` — which the kernel spreads over a group's heads with lane
rolls (a product with a 0/1 matrix, tried first, cost 0.3 ms a block more),
so no logits-shaped plane comes from HBM; the is-self ``1 / std`` as
float32 lane rows ``inv (B·8, g·A)``. Both are made once a step
(:func:`tables`) and read by every block.

Numerics (pinned against ``_entity_attention_heads`` in
``tests/test_kernels.py``): MXU operands in the compute dtype with
float32 accumulation, rounded to the compute dtype where the XLA form
rounds them (``q``, ``k_0``, ``v_0``, both tables, the probabilities,
the context); logits and softmax statistics are float32 at every dtype,
where the XLA form's bf16 mode rounds the logits to bf16 — never less
exact. Forward only: acting takes it, the learner's differentiated
unroll keeps the XLA association (``agent_forward_qslice_entity``).

Compiled (Mosaic) is the only mode the program uses: where the lowering
is not for a TPU the forward runs its XLA association instead
(``jax.lax.platform_dependent``), never the interpreter. :data:`INTERPRET`
is for the CPU parity tests alone, which set it to run the forward THROUGH
the kernel on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: True = the entity forward calls the kernel in interpreter mode on
#: whatever platform it is lowered for. Tests flip it; the program never.
INTERPRET = False

#: lanes of a vector register / MXU tile: a head group's width
_LANES = 128
#: sublane quantum that serves both float32 (8) and bfloat16 (16) tiles:
#: ``A`` a multiple of it keeps every per-env row slice on a tile boundary
_SUBLANE = 16
#: query rows a grid step projects at once (several envs, so the MXU sees
#: far more than the 64 rows of one env)
_TILE_ROWS = 512
#: the nine entity features, zero-padded to one bf16 sublane tile
_FEATS = 16
#: rows of a float32 sublane tile: an env's ``inv`` row is stored eight
#: times so that its slice starts on a tile
_ROWS = 8
#: additive mask of an unseen table row: ``exp`` of it is exactly 0 beside
#: any finite logit, and key 0 is always finite
_NEG = -1e30


def _env_tile(b: int, a: int):
    """Envs a grid step: the largest divisor of ``b`` whose rows fit
    ``_TILE_ROWS``; ``None`` where one env's logits tiles would outgrow
    VMEM (more agents than a lane tile: never compiled)."""
    if a > _LANES:
        return None
    return max(t for t in range(1, b + 1)
               if b % t == 0 and t * a <= _TILE_ROWS)


def group(hd: int, heads: int) -> int:
    """Heads a 128-lane group."""
    return _LANES // (hd // heads)


def eligible(b: int, a: int, emb: int, hd: int, heads: int) -> bool:
    """Shapes this kernel is written for — all of them known when the
    forward is traced: head groups that fill whole 128-lane tiles
    (``128 % head_dim == 0``, ``H·D`` a multiple of 128), lane-dense
    activations, and ``A`` on the sublane quantum and within a lane
    tile."""
    d = hd // heads
    return (d * heads == hd and _LANES % d == 0 and hd % _LANES == 0
            and emb % _LANES == 0 and a % _SUBLANE == 0
            and _env_tile(b, a) is not None)


def tables(feats: jnp.ndarray, inv_self: jnp.ndarray, seen: jnp.ndarray,
           dtype, g: int):
    """The per-step inputs every block's kernel call shares, in the
    kernel's layout (module docstring), for :func:`group` ``g``: ``feats
    (B, 2A, 9)`` → ``(B·2A, 16)``; ``seen (B, 2A, A)`` bool → the additive
    ``mask (B·2A, 128)``, zero past lane ``A``; ``inv (B·8, g·A)`` float32
    from ``inv_self (B, A, 1)``, a sublane tile of eight equal rows an
    env."""
    b, a2, f = feats.shape
    a = a2 // 2
    feats = jnp.pad(feats.astype(dtype), ((0, 0), (0, 0), (0, _FEATS - f)))
    mask = jnp.pad(jnp.where(seen, 0.0, _NEG).astype(dtype).reshape(b * a2, a),
                   ((0, 0), (0, _LANES - a)))
    inv = jnp.broadcast_to(
        jnp.concatenate([inv_self.astype(jnp.float32).reshape(b, 1, a)] * g,
                        axis=-1), (b, _ROWS, g * a))
    return (feats.reshape(b * a2, _FEATS), mask,
            inv.reshape(b * _ROWS, g * a))


def _kernel(x0_ref, h_ref, feats_ref, mask_ref, inv_ref, wq_ref, wk_ref,
            wv_ref, wu_ref, wek_ref, wev_ref, bek_ref, bev_ref, ub_ref,
            out_ref, q_s, k0_s, v0_s, ke_s, ve_s, ctx_s, *, tile: int,
            a: int, d: int, g: int):
    """One grid step: ``tile`` envs. Projections for the whole tile
    (``tile·A`` rows), then per env and head group logits → masked
    softmax → context against scratch that never leaves VMEM, then the
    ``wu`` product for the whole tile."""
    f32 = jnp.float32
    dt = q_s.dtype
    hd = q_s.shape[1]
    gw = g * d                                    # lanes of a head group
    a2, ga = 2 * a, g * a

    # bf16 operands take the MXU's one pass whatever the process's default
    # matmul precision says (Mosaic has no multi-pass form for them);
    # float32 operands follow that default, as the XLA form does
    prec = None if dt == f32 else jax.lax.Precision.DEFAULT

    def dot(x, y, contract=((1,), (0,))):         # float32 accumulation
        return jax.lax.dot_general(x, y, (contract, ((), ())), precision=prec,
                                   preferred_element_type=f32)

    def dot_nt(x, y):                             # x · yᵀ
        return dot(x, y, ((1,), (1,)))

    def dot_tn(x, y):                             # xᵀ · y
        return dot(x, y, ((0,), (0,)))

    def proj(x, w_ref):
        return dot(x, w_ref[...]).astype(dt)

    def table(w_ref, b_ref):
        return (dot(feats_ref[...], w_ref[...]) + b_ref[...]).astype(dt)

    q_s[...] = proj(x0_ref[...], wq_ref)
    k0_s[...] = proj(h_ref[...], wk_ref)
    v0_s[...] = proj(h_ref[...], wv_ref)
    ke_s[...] = table(wek_ref, bek_ref)
    ve_s[...] = table(wev_ref, bev_ref)

    # (2A, g·A): the agent of query column (h, a) against key row j — its
    # own visible-table row (j = a) and its own hidden token (j = A + a)
    row = jax.lax.broadcasted_iota(jnp.int32, (a2, ga), 0)
    agent = jax.lax.broadcasted_iota(jnp.int32, (a2, ga), 1)
    for h in range(1, g):
        agent = jnp.where(agent >= a, agent - a, agent)
    own, key0 = row == agent, row == agent + a
    # (A, gw) a head: its lanes within the group
    glane = jax.lax.broadcasted_iota(jnp.int32, (a, gw), 1)
    head = [(glane >= h * d) & (glane < (h + 1) * d) for h in range(g)]
    # the is-self key and value of feature 8, a row an agent, per group
    self_k = {lo: jnp.broadcast_to(wek_ref[8:9, lo:lo + gw], (a, gw))
              for lo in range(0, hd, gw)}
    self_v = {lo: jnp.broadcast_to(wev_ref[8:9, lo:lo + gw], (a, gw))
              for lo in range(0, hd, gw)}

    # unrolled, not a loop: an env's chain (product → softmax → product) is
    # latency-bound, and only straight-line code lets the scheduler fill
    # one env's waits with another's work (0.56 against 1.38 ms a block)
    for e in range(tile):
        r, t = slice(e * a, (e + 1) * a), slice(e * a2, (e + 1) * a2)
        # (2A, g·A): every head's columns see the same rows — the A mask
        # lanes (zeros after them) added to themselves rolled a head on
        one = mask_ref[t, :].astype(f32)
        mask = one
        for h in range(1, min(g, _LANES // a)):
            mask = mask + pltpu.roll(one, h * a, 1)
        if ga < _LANES:
            mask = mask[:, :ga]
        elif ga > _LANES:
            mask = jnp.concatenate([mask] * (ga // _LANES), axis=1)
        inv = inv_ref[e * _ROWS:e * _ROWS + 1, :]             # (1, g·A)
        for lo in range(0, hd, gw):
            cols = slice(lo, lo + gw)
            qg = q_s[r, cols]
            if g > 1:
                # a head is a block mask on the (h, a) expanded query rows
                qf = qg.astype(f32)
                qx = jnp.concatenate(
                    [jnp.where(head[h], qf, 0.0) for h in range(g)],
                    axis=0).astype(dt)
            else:
                qx = qg
            l1 = dot_nt(ke_s[t, cols], qx)
            l2 = dot_nt(jnp.concatenate([self_k[lo], k0_s[r, cols]],
                                        axis=0), qx)
            lg1 = l1 + jnp.where(own, l2 * inv, 0.0) + mask
            lg2 = jnp.where(key0, l2, _NEG)
            top = jnp.max(jnp.maximum(lg1, lg2), axis=0, keepdims=True)
            e1, e2 = jnp.exp(lg1 - top), jnp.exp(lg2 - top)
            den = 1.0 / jnp.sum(e1 + e2, axis=0, keepdims=True)
            p1 = e1 * den
            # rows [0, A): the diagonal's probability times 1 / std, for
            # the is-self value; rows [A, 2A): key 0's, for v_0
            p2 = jnp.where(own, p1 * inv, e2 * den)
            full = (dot_tn(p1.astype(dt), ve_s[t, cols])
                    + dot_tn(p2.astype(dt), jnp.concatenate(
                        [self_v[lo], v0_s[r, cols]], axis=0)))  # (g·A, gw)
            # of every (h, a) row's context over all the group's lanes,
            # its own head's
            ctx = full[:a]
            for h in range(1, g):
                ctx = jnp.where(head[h], full[h * a:(h + 1) * a], ctx)
            ctx_s[r, cols] = ctx.astype(dt)

    out_ref[...] = dot(ctx_s[...], wu_ref[...]) + ub_ref[...]


def entity_attention(hp: dict, x0: jnp.ndarray, h_tok: jnp.ndarray,
                     feats: jnp.ndarray, mask: jnp.ndarray,
                     inv: jnp.ndarray, *, heads: int,
                     interpret: bool = False) -> jnp.ndarray:
    """``_entity_attention_heads`` as one kernel: ``hp`` one block of
    ``_fold_entity_heads``, ``x0`` / ``h_tok (B, A, E)`` in the compute
    dtype, ``feats`` / ``mask`` / ``inv`` from :func:`tables`. Returns
    ``attended (B·A, E)`` float32. The caller checks :func:`eligible`."""
    b, a, emb = x0.shape
    dt = x0.dtype
    hd = hp["wq"].shape[1]
    d = hd // heads
    g = group(hd, heads)
    tile = _env_tile(b, a)
    rows, a2 = tile * a, 2 * a
    f32 = jnp.float32

    def pad9(w):                                  # (9, H·D) → (16, H·D)
        return jnp.pad(w.astype(dt), ((0, _FEATS - w.shape[0]), (0, 0)))

    def row(v):
        return v.astype(f32).reshape(1, -1)

    def per_tile(r, c):
        return pl.BlockSpec((r, c), lambda i: (i, 0))

    def whole(r, c):                              # fetched once
        return pl.BlockSpec((r, c), lambda i: (0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, a=a, d=d, g=g),
        grid=(b // tile,),
        in_specs=[per_tile(rows, emb), per_tile(rows, emb),
                  per_tile(tile * a2, _FEATS), per_tile(tile * a2, _LANES),
                  per_tile(tile * _ROWS, g * a),
                  whole(emb, hd), whole(emb, hd), whole(emb, hd),
                  whole(hd, emb), whole(_FEATS, hd), whole(_FEATS, hd),
                  whole(1, hd), whole(1, hd), whole(1, emb)],
        out_specs=per_tile(rows, emb),
        out_shape=jax.ShapeDtypeStruct((b * a, emb), f32),
        scratch_shapes=[pltpu.VMEM((rows, hd), dt)] * 3
        + [pltpu.VMEM((tile * a2, hd), dt)] * 2
        + [pltpu.VMEM((rows, hd), dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x0.reshape(b * a, emb), h_tok.reshape(b * a, emb), feats, mask, inv,
      hp["wq"].astype(dt), hp["wk"].astype(dt), hp["wv"].astype(dt),
      hp["wu"].astype(dt), pad9(hp["wek"]), pad9(hp["wev"]),
      row(hp["bek"]), row(hp["bev"]), row(hp["u_bias"]))
