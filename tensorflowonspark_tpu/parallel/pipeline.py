"""Pipeline parallelism: GPipe-style microbatching over a ``stage`` axis.

SURVEY.md §2.3: the reference has no PP; this is a mesh-native extension.
Stage parameters live stacked on a leading stage dimension sharded over
``stage``; activations flow stage-to-stage with ``ppermute`` (XLA
collective-permute over ICI) in a static schedule of M + P - 1 ticks
(fill + drain). Every rank runs the same jitted body (SPMD), so there is
no per-stage program — the stage's own parameter shard selects its role.
"""

import functools

import jax
import jax.numpy as jnp


def pipeline_apply(stage_fn, stage_params, microbatches, mesh,
                   stage_axis="stage"):
    """Run microbatches through P pipeline stages.

    Args:
      stage_fn: ``(params_for_stage, x) -> y`` with y.shape == x.shape
        (equal-width stages — the classic PP layout).
      stage_params: pytree whose leaves have leading dim P (one slice per
        stage), sharded ``PartitionSpec(stage_axis, ...)``.
      microbatches: [M, mb, ...] array (replicated input).
      mesh: mesh with ``stage_axis``.

    Returns [M, mb, ...]: outputs of the last stage, replicated.
    """
    from jax.sharding import PartitionSpec as P

    num_stages = mesh.shape[stage_axis]
    num_micro = microbatches.shape[0]

    params_spec = jax.tree.map(lambda _: P(stage_axis), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(params_spec, P()), out_specs=P(),
        check_vma=False)
    def _run(params, xs):
        rank = jax.lax.axis_index(stage_axis)
        local_params = jax.tree.map(lambda p: p[0], params)  # [1,...] -> [...]
        mb_shape = xs.shape[1:]
        # carry dtype = stage OUTPUT dtype (may differ from xs, e.g. f32
        # activations out of bf16 inputs); a mismatch would fail the
        # fori_loop carry structure check
        out_aval = jax.eval_shape(stage_fn, local_params, xs[0])
        out_dtype = out_aval.dtype
        fwd_perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        def tick(t, carry):
            carried, outputs = carry
            # stage 0 ingests microbatch t (while t < M); others take the
            # activation permuted from their predecessor last tick
            inject = xs[jnp.minimum(t, num_micro - 1)].astype(out_dtype)
            x_in = jnp.where(rank == 0, inject, carried)
            y = stage_fn(local_params, x_in)
            # last stage banks its result for microbatch t-(P-1)
            out_idx = t - (num_stages - 1)
            valid = (rank == num_stages - 1) & (out_idx >= 0)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outputs)
            carried = jax.lax.ppermute(y, stage_axis, fwd_perm)
            return carried, outputs

        carried = jnp.zeros(mb_shape, out_dtype)
        outputs = jnp.zeros((num_micro,) + mb_shape, out_dtype)
        _, outputs = jax.lax.fori_loop(
            0, num_micro + num_stages - 1, tick, (carried, outputs))
        # outputs are only real on the last stage; broadcast them
        outputs = jax.lax.psum(
            jnp.where(rank == num_stages - 1, outputs, 0.0), stage_axis)
        return outputs

    return _run(stage_params, microbatches)


def stack_stage_params(init_fn, rng, num_stages, sample_x):
    """Initialize P stage params stacked on a leading dim (vmapped init)."""
    rngs = jax.random.split(rng, num_stages)
    return jax.vmap(lambda r: init_fn(r, sample_x))(rngs)
