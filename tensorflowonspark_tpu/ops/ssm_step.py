"""One decode step of a Mamba-2 state-space layer, for the live rows only.

The token step of a model with a recurrent state a slot
(models/falcon_h1.py): every slot keeps, per layer, an SSM state
``S [heads, head_dim, d_state]`` and the last ``d_conv - 1`` inputs of
the causal convolution. A step of head ``e`` (group ``g``) of a row is::

    dA  = exp(dt_e * A_e)                       (float32, dt after softplus)
    S'  = dA * S + (dt_e * x_e) (outer) B_g     [head_dim, d_state]
    y_e = S' C_g + D_e * x_e                     [head_dim]
    conv' = conv[1:] ++ [xBC]                    the convolution's inputs

where ``x``, ``B``, ``C`` are the convolution's outputs of this position
(computed by the caller from ``conv`` and ``xBC``, the position's input
to it). The state is the heavy side: 4 MB a row and layer at Falcon-H1's
widths, read and written once a step, while everything else of the row
is a few KB.

Two formulations:

- ``impl="pallas"`` -- the TPU kernel, a ``pallas_call`` named
  ``ssm_step``. Its grid is a WORK LIST of the live rows (as
  ops/paged_attention.py's is of live blocks): ``n_live x head_blocks``
  steps, a traced bound, each step one row's block of ``block_heads``
  heads, read and written in place (``input_output_aliases``; the
  caller donates the state). A row the caller marks idle is neither
  read nor written: unlike K/V, a state has no scratch row to spill
  into, and a slot between two requests keeps what it holds until a
  prefill writes the row whole. The lists are one entry longer than
  the live steps and that entry repeats the last (the pipeline asks
  the index maps for the step AHEAD: PR 34). Where no row is live the
  grid still takes one row's blocks, and writes them back as they were.
  The convolution's inputs shift on a row's first head block; the
  skip ``D x`` is added in the kernel.
- ``impl="xla"`` -- the same step in plain ``jax.numpy`` over the
  gathered live rows, scattered back (idle rows dropped by index): the
  off-TPU path and the kernel's oracle.

Layout. Per step the kernel holds ``[block_heads, head_dim, d_state]``
of the state (2 MiB at 16 heads of 128 x 256 in float32) and the
column vectors the outer product needs (``x`` and the output per head)
as ``[head_dim, block_heads]`` tiles, the heads in lanes: a head's
column is picked out by a masked lane reduction, so the kernel slices
no lane of a vector.
"""

import functools

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops.flash_attention import on_tpu

#: heads of one grid step (at most; a divisor of a group's heads)
BLOCK_HEADS = 16


def block_heads(heads, groups):
    """Heads a grid step takes: the largest divisor of a group's heads
    up to :data:`BLOCK_HEADS`, so that a step's heads share one ``B``
    and ``C``."""
    per_group = heads // groups
    return max(h for h in range(1, min(BLOCK_HEADS, per_group) + 1)
               if per_group % h == 0)


def live_rows(live):
    """``(n_live, order)``: the number of live rows and the batch rows,
    live ones first in their order, as int32 (what both formulations
    walk)."""
    live = live.astype(bool)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return jnp.sum(live, dtype=jnp.int32), order


def _step_xla(state, conv, x, dt, a, b, c, d, xbc, rows, live):
    s, nh, p = x.shape
    groups = b.shape[1]
    rep = nh // groups
    f32 = jnp.float32
    at = rows.astype(jnp.int32)
    old = state[at].astype(f32)                           # [S, nh, P, N]
    bh = jnp.repeat(b.astype(f32), rep, axis=1)           # [S, nh, N]
    ch = jnp.repeat(c.astype(f32), rep, axis=1)
    x32 = x.astype(f32)
    decay = jnp.exp(dt * a[None, :])                      # [S, nh]
    new = decay[..., None, None] * old \
        + (dt[..., None] * x32)[..., None] * bh[:, :, None, :]
    y = jnp.sum(new * ch[:, :, None, :], axis=-1) + d[None, :, None] * x32
    # idle rows: an index past the end, dropped by the scatter
    gone = jnp.where(live, at, state.shape[0])
    state = state.at[gone].set(new.astype(state.dtype), mode="drop")
    window = jnp.concatenate(
        [conv[at][:, 1:], xbc[:, None, :].astype(conv.dtype)], axis=1)
    conv = conv.at[gone].set(window, mode="drop")
    return jnp.where(live[:, None, None], y, 0.0), state, conv


def _kernel(brow_ref, srow_ref, n_ref, x_ref, dt_ref, a_ref, d_ref, b_ref,
            c_ref, xbc_ref, s_in, conv_in, y_ref, s_out, conv_out, *,
            head_blocks, heads):
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    f32 = jnp.float32

    @pl.when(step < n_ref[0] * head_blocks)
    def _live():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)

        def column(v, h):                    # [R, heads] -> [R, 1]
            return jnp.sum(jnp.where(lane == h, v, 0.0), axis=1,
                           keepdims=True)

        bv = b_ref[0, 0].astype(f32)         # [1, N]
        cv = c_ref[0, 0].astype(f32)         # [1, N]
        xs = x_ref[0, 0].astype(f32)         # [P, heads]
        dt = dt_ref[0, 0]                    # [1, heads]
        decay = jnp.exp(dt * a_ref[0])       # [1, heads]
        dv = d_ref[0]                        # [1, heads]
        y = jnp.zeros(xs.shape, f32)
        for h in range(heads):
            xh = column(xs, h)                                # [P, 1]
            new = column(decay, h) * s_in[0, h].astype(f32) \
                + (column(dt, h) * xh) * bv                   # [P, N]
            s_out[0, h] = new.astype(s_out.dtype)
            yh = jnp.sum(new * cv, axis=1, keepdims=True) \
                + column(dv, h) * xh                          # [P, 1]
            y = y + jnp.where(lane == h, yh, 0.0)
        y_ref[0, 0] = y

        @pl.when(step % head_blocks == 0)
        def _shift():
            k1 = conv_in.shape[1]
            if k1 > 1:
                conv_out[0, :k1 - 1, :] = conv_in[0, 1:, :]
            conv_out[0, k1 - 1:, :] = xbc_ref[0].astype(conv_out.dtype)

    @pl.when(n_ref[0] == 0)
    def _none_live():
        # every step sits on one row's blocks: written back unchanged
        s_out[...] = s_in[...]
        conv_out[...] = conv_in[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


def _step_pallas(state, conv, x, dt, a, b, c, d, xbc, rows, live,
                 interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, nh, p = x.shape
    n = state.shape[-1]
    groups = b.shape[1]
    hb = block_heads(nh, groups)
    blocks = nh // hb
    per_group = nh // groups
    k1, width = conv.shape[1], conv.shape[2]
    f32 = jnp.float32
    n_live, order = live_rows(live)
    # one entry past the live ones, repeating the last (or row 0)
    last = order[jnp.maximum(n_live - 1, 0)]
    brow = jnp.concatenate([order, last[None]])
    brow = jnp.where(jnp.arange(s + 1) < n_live, brow, last)
    srow = rows.astype(jnp.int32)[brow]
    # the heads in lanes: [S, blocks, P, hb] and [S, blocks, 1, hb]
    xt = x.reshape(s, blocks, hb, p).transpose(0, 1, 3, 2)
    dtb = dt.astype(f32).reshape(s, blocks, 1, hb)
    ab = a.astype(f32).reshape(blocks, 1, hb)
    db = d.astype(f32).reshape(blocks, 1, hb)
    b4 = b.reshape(s, groups, 1, n)
    c4 = c.reshape(s, groups, 1, n)
    xbc3 = xbc.reshape(s, 1, width)

    def item(i, br, sr, nl):
        return jnp.minimum(i // blocks, s)

    def by_batch(i, br, sr, nl):
        return (br[item(i, br, sr, nl)], i % blocks, 0, 0)

    def by_state(i, br, sr, nl):
        return (sr[item(i, br, sr, nl)], i % blocks, 0, 0)

    def by_group(i, br, sr, nl):
        return (br[item(i, br, sr, nl)], (i % blocks) * hb // per_group,
                0, 0)

    def by_head(i, br, sr, nl):
        return (i % blocks, 0, 0)

    def conv_row(i, br, sr, nl):
        return (sr[item(i, br, sr, nl)], 0, 0)

    def xbc_row(i, br, sr, nl):
        return (br[item(i, br, sr, nl)], 0, 0)

    state_spec = pl.BlockSpec((1, hb, p, n), by_state)
    conv_spec = pl.BlockSpec((1, k1, width), conv_row)
    col_spec = pl.BlockSpec((1, 1, p, hb), by_batch)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(jnp.maximum(n_live, 1) * blocks,),
        in_specs=[col_spec,
                  pl.BlockSpec((1, 1, 1, hb), by_batch),
                  pl.BlockSpec((1, 1, hb), by_head),
                  pl.BlockSpec((1, 1, hb), by_head),
                  pl.BlockSpec((1, 1, 1, n), by_group),
                  pl.BlockSpec((1, 1, 1, n), by_group),
                  pl.BlockSpec((1, 1, width), xbc_row),
                  state_spec, conv_spec],
        out_specs=[col_spec, state_spec, conv_spec],
    )
    kernel = functools.partial(_kernel, head_blocks=blocks, heads=hb)
    yt, state, conv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(xt.shape, f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
        # operands count the three scalar lists: the state is input 10,
        # the convolution's inputs 11
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="ssm_step",
    )(brow, srow, n_live[None], xt, dtb, ab, db, b4, c4, xbc3, state, conv)
    y = yt.transpose(0, 1, 3, 2).reshape(s, nh, p)
    return jnp.where(live[:, None, None], y, 0.0), state, conv


def ssm_step(state, conv, x, dt, a, b, c, d, xbc, rows, live, impl=None,
             interpret=None):
    """One step of every live row (module docstring).

    ``state [R, heads, head_dim, d_state]`` and ``conv [R, d_conv - 1,
    width]`` are the layer's rows (a slot a row), ``x [S, heads,
    head_dim]``, ``dt [S, heads]`` (float32, after softplus), ``b``/``c``
    ``[S, groups, d_state]``, ``xbc [S, width]`` the S batch rows of the
    call, ``rows [S]`` the state row of each and ``live [S]`` which of
    them step. ``a``/``d`` ``[heads]``. Returns ``(y [S, heads,
    head_dim] float32, 0 on idle rows, state', conv')``; idle rows' state
    and convolution rows are left as they were, bit for bit."""
    if impl in (None, "auto"):
        impl = "pallas" if on_tpu() else "xla"
    if impl == "xla":
        return _step_xla(state, conv, x, dt, a, b, c, d, xbc, rows, live)
    if impl == "pallas":
        if interpret is None:
            interpret = not on_tpu()
        return _step_pallas(state, conv, x, dt, a, b, c, d, xbc, rows,
                            live, interpret)
    raise ValueError("unknown ssm_step impl {!r}; expected None/'auto', "
                     "'pallas' or 'xla'".format(impl))
