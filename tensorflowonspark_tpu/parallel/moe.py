"""Mixture-of-Experts with expert parallelism over an ``expert`` axis.

SURVEY.md §2.3: absent from the reference; mesh-native extension. Experts'
FFN weights are sharded one-per-rank over the ``expert`` axis; tokens are
routed with top-1 (switch-style) gating. Dispatch is the dense-einsum
formulation: each rank runs its resident experts over the FULL token set
and masks by the routing one-hots, then a ``psum`` combines. That trades
FLOPs (every expert sees every token — there is no capacity truncation)
for *zero* ragged communication — the all-to-all becomes a single
all-reduce XLA schedules over ICI — and keeps every shape static, which
is what the TPU compiler wants. Right for moderate expert counts; a
capacity-bounded ragged-a2a dispatch is the later optimization for large
E.
"""

import functools

import jax
import jax.numpy as jnp


def top1_gating(logits):
    """[T, E] router logits -> (one_hot [T, E], probs [T], aux_loss).

    Aux loss is the switch-transformer load-balance term (mean gate prob *
    token fraction per expert, scaled by E^2 so perfectly balanced == 1).
    """
    num_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    one_hot = jax.nn.one_hot(idx, num_experts, dtype=probs.dtype)
    gate = jnp.sum(probs * one_hot, axis=-1)
    density = one_hot.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux = jnp.sum(density * density_proxy) * (num_experts ** 2)
    return one_hot, gate, aux


def moe_ffn(x, router_w, w_in, w_out, mesh, expert_axis="expert",
            activation=jax.nn.gelu):
    """Expert-parallel FFN layer.

    Args:
      x: [tokens, hidden] (replicated over the expert axis).
      router_w: [hidden, E] routing weights (replicated).
      w_in: [E, hidden, ffn] expert up-projections, sharded (expert_axis,).
      w_out: [E, ffn, hidden] expert down-projections, sharded likewise.

    Returns ([tokens, hidden], aux_loss).
    """
    from jax.sharding import PartitionSpec as P

    num_experts = w_in.shape[0]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), P(expert_axis), P(expert_axis)),
        out_specs=(P(), P()),
        check_vma=False)
    def _moe(x, router_w, w_in_local, w_out_local):
        rank = jax.lax.axis_index(expert_axis)
        experts_per_rank = w_in_local.shape[0]

        logits = x @ router_w  # [T, E]
        one_hot, gate, aux = top1_gating(logits)

        # my experts' global ids: [e_local]
        first = rank * experts_per_rank
        # mask of tokens routed to each of my local experts: [T, e_local]
        local_mask = jax.lax.dynamic_slice_in_dim(
            one_hot, first, experts_per_rank, axis=1)

        # dense dispatch: every rank runs its experts over all tokens,
        # masked — ragged a2a avoided, shapes static
        h = jnp.einsum("th,ehf->etf", x, w_in_local)
        h = activation(h)
        y_local = jnp.einsum("etf,efh->eth", h, w_out_local)
        combined = jnp.einsum("eth,te->th", y_local,
                              local_mask * gate[:, None])
        y = jax.lax.psum(combined, expert_axis)
        return y.astype(x.dtype), aux

    return _moe(x, router_w, w_in, w_out)


def init_moe_params(rng, num_experts, hidden, ffn, dtype=jnp.float32):
    """(router_w, w_in, w_out) with switch-style scaled init."""
    k1, k2, k3 = jax.random.split(rng, 3)
    router_w = jax.random.normal(k1, (hidden, num_experts), dtype) * 0.02
    w_in = jax.random.normal(k2, (num_experts, hidden, ffn), dtype) \
        * (2.0 / hidden) ** 0.5
    w_out = jax.random.normal(k3, (num_experts, ffn, hidden), dtype) \
        * (2.0 / ffn) ** 0.5
    return router_w, w_in, w_out
