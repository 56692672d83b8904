"""A sparse expert layer without capacity: route, sort, grouped matmul.

The feed-forward of a mixture-of-experts decoder (models/sdar_moe.py):
every position picks ``top_k`` of ``E`` experts by a softmax router and
its output is the gate-weighted sum of those experts' gated MLPs. What
this module does NOT do is run every expert over every position (the
dense formulation of parallel/moe.py, ``E / top_k`` times the work) or
give an expert a fixed capacity and drop what overflows: the
``positions x top_k`` assignments are SORTED by expert and each expert
multiplies exactly the rows routed to it, however uneven the routing.

The chip's share: the layer is told which experts it holds (``first``
and, by the leading axis of the weights it is given, how many). It
routes over all ``E`` at the published width, computes the part of the
result its own experts give, and leaves out what the absent ones would
have added; the parts of all holders sum to the whole layer
(tests/test_sdar_moe.py).

Layout. Sorted rows are laid out TILE-ALIGNED: expert ``e``'s rows start
at a multiple of the row tile ``tm`` and are padded up to one, so a tile
of ``tm`` rows belongs to ONE expert and the grouped product is
``out[tile] = lhs[tile] @ rhs[expert_of(tile)]`` with no masking inside.
The padded length is static (``M + held * (tm - 1)`` rounded up: the
worst routing), the number of live tiles is data. Two formulations
share that layout:

- ``impl="pallas"`` — the TPU kernel, a ``pallas_call`` named
  ``expert_gmm``: one grid step per row tile, the tile's expert read
  from a scalar-prefetched table by the weight BlockSpec's index map, so
  consecutive tiles of one expert re-use the weights already in VMEM and
  each held expert's matrix is fetched once per call; tiles past the
  live count write zeros and fetch nothing new.
- ``impl="ragged"`` — ``jax.lax.ragged_dot`` over the same padded rows
  (group sizes = the padded sizes): the off-TPU path and the kernel's
  oracle.
"""

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops.flash_attention import on_tpu


def row_tile(assignments, held):
    """Rows per tile for ``assignments`` rows over ``held`` experts: the
    mean group size rounded up to a power of two, at least 16 (one
    bfloat16 sublane tile) and at most 128 (the MXU's rows)."""
    mean = max(1, -(-assignments // held))
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def route(h, router, top_k):
    """Softmax router in float32 over ALL experts: ``(experts [T, k]
    int32, gates [T, k] float32)`` with the gates of a position
    normalised over its ``top_k`` (``norm_topk_prob``)."""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
    top, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return experts.astype(jnp.int32), \
        top / jnp.sum(top, axis=-1, keepdims=True)


def plan(experts, num_experts, first, held, tm):
    """Where each assignment's row goes in the tile-aligned layout.

    ``experts [T, k]`` are the routed expert ids. Returns a dict:
    ``counts [E]`` positions routed to each expert (held or not),
    ``dest [T, k]`` the padded row of each assignment (``rows`` - one
    past the end - for an expert that is not held), ``row_token [rows]``
    the position each padded row reads (0 for padding), ``sizes [held]``
    the padded group sizes, ``tile_expert [tiles]`` each tile's (local)
    expert and ``live`` the number of tiles that hold rows."""
    t, k = experts.shape
    m = t * k
    rows = -(-(m + held * (tm - 1)) // tm) * tm
    flat = experts.reshape(m)
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    local = flat - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    sizes = counts[first:first + held]
    padded = -(-sizes // tm) * tm
    # one more entry so that key == held (not held here) indexes in range
    start = jnp.concatenate([jnp.cumsum(sizes) - sizes, jnp.array([m])])
    start_pad = jnp.concatenate([jnp.cumsum(padded) - padded,
                                 jnp.array([rows])])
    dest_s = jnp.where(key_s < held,
                       start_pad[key_s] + jnp.arange(m) - start[key_s],
                       rows)
    dest = jnp.zeros((m,), jnp.int32).at[order].set(dest_s.astype(jnp.int32))
    row_token = jnp.zeros((rows,), jnp.int32).at[dest].set(
        jnp.arange(m, dtype=jnp.int32) // k, mode="drop")
    tiles = padded // tm
    live = jnp.sum(tiles)
    ends = jnp.cumsum(tiles)
    tile_expert = jnp.searchsorted(ends, jnp.arange(rows // tm),
                                   side="right").astype(jnp.int32)
    # tiles past the live ones name the last live tile's expert: the
    # kernel's weight index then does not change and nothing is fetched
    last = jnp.minimum(tile_expert[jnp.maximum(live - 1, 0)], held - 1)
    tile_expert = jnp.where(jnp.arange(rows // tm) < live, tile_expert,
                            last)
    return {"counts": counts, "dest": dest.reshape(t, k),
            "row_token": row_token, "sizes": padded.astype(jnp.int32),
            "tile_expert": tile_expert, "live": live.astype(jnp.int32)}


def _gmm_kernel(tile_expert_ref, live_ref, lhs_ref, rhs_ref, out_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i < live_ref[0])
    def _multiply():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[0],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(i >= live_ref[0])
    def _nothing_routed_here():
        out_ref[...] = jnp.zeros_like(out_ref)


def _pallas(lhs, rhs, tile_expert, live, tm, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, kdim = lhs.shape
    ndim = rhs.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows // tm,),
        in_specs=[
            pl.BlockSpec((tm, kdim), lambda i, te, lv: (i, 0)),
            pl.BlockSpec((1, kdim, ndim), lambda i, te, lv: (te[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, ndim), lambda i, te, lv: (i, 0)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, ndim), lhs.dtype),
        # tiles of one expert follow each other and share its weights:
        # the axis is walked in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="expert_gmm",
    )(tile_expert, live.reshape(1), lhs, rhs)


def expert_gmm(lhs, rhs, layout, tm, impl=None, interpret=None):
    """Grouped matrix product over tile-aligned rows: ``out[r] = lhs[r]
    @ rhs[expert of r's tile]`` for ``lhs [rows, K]``, ``rhs [held, K,
    N]`` and a ``layout`` from :func:`plan`; rows of tiles that hold
    nothing come out zero. Accumulates in float32, returns ``lhs``'s
    dtype. ``impl`` None picks the kernel on TPU and ``ragged_dot``
    elsewhere."""
    if impl is None:
        impl = "pallas" if on_tpu() else "ragged"
    if impl == "pallas":
        if interpret is None:
            interpret = not on_tpu()
        return _pallas(lhs, rhs, layout["tile_expert"], layout["live"], tm,
                       interpret)
    if impl == "ragged":
        return jax.lax.ragged_dot(
            lhs, rhs, layout["sizes"],
            preferred_element_type=jnp.float32).astype(lhs.dtype)
    raise ValueError("unknown expert_gmm impl {!r}; expected None, "
                     "'pallas' or 'ragged'".format(impl))


def expert_layer(h, router, gate, up, down, top_k, first=0, scale=1.0):
    """The held experts' part of a sparse gated-MLP layer.

    ``h [T, H]``; ``router [H, E]`` over ALL experts; ``gate``/``up``
    ``[held, H, F]`` and ``down [held, F, H]`` the weights of experts
    ``first .. first + held - 1``. Returns ``(y [T, H] in h's dtype,
    experts [T, top_k] int32)``: ``y[t] = sum over t's top_k experts
    that are held of scale * gate_weight * down(silu(gate(h[t])) *
    up(h[t]))``, and the experts (of all ``E``) the router sent each
    position to."""
    t, held = h.shape[0], gate.shape[0]
    experts, gates = route(h, router, top_k)
    if scale != 1.0:
        gates = gates * scale
    tm = row_tile(t * top_k, held)
    layout = plan(experts, router.shape[1], first, held, tm)
    x = h[layout["row_token"]]
    act = (jax.nn.silu(expert_gmm(x, gate, layout, tm).astype(jnp.float32))
           * expert_gmm(x, up, layout, tm).astype(jnp.float32)
           ).astype(h.dtype)
    out = expert_gmm(act, down, layout, tm)
    # back to positions: each reads its top_k rows (none for an expert
    # that is not held: the row index is past the end and fills with 0)
    picked = jnp.take(out, layout["dest"], axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)
    y = jnp.sum(picked * gates[..., None], axis=1)
    return y.astype(h.dtype), experts
