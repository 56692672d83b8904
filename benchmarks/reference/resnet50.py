"""Plain reference for the ``resnet50`` configuration: ResNet v1.5
(He et al. 2015, arXiv:1512.03385, Table 1; stride 2 on the 3x3 of a
bottleneck), its loss, gradients and three steps of SGD with momentum.

Straightforward ``jax.numpy``/``lax`` in float32 with ``highest`` matmul
precision. It imports nothing of the program and takes nothing the
program made: the weights come from :func:`init_params` (the
benchmark's own, from the seed) and the batches from the benchmark's
generator. Each bottleneck is rematerialised in the backward pass so
that batch 256 at 224 px fits one chip in float32 - that changes what
is stored, not what is computed.

``precision`` selects what the convolutions and the dense layer
multiply in: ``"float32"`` (the reference), ``"bfloat16"`` (what the
configuration states) or ``"float8"`` (the control: operands rounded to
``float8_e4m3fn``, one step below bfloat16).

Departures from the paper, the program's own and followed here: no
bias in convolutions, BatchNorm eps 1e-5, inputs are raw uint8 pixel
values cast to float (no mean/std normalisation), labels are integers.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
#: scale of the last BatchNorm of every bottleneck in the benchmark's
#: weights. At 1 (sixteen undamped residual branches) the BatchNorm
#: gradients of the first step are rounding noise: the bfloat16 and the
#: float32 forms of THIS reference then disagree by 0.22 of a leaf's
#: norm and their gradients have a cosine of 0.1-0.4 (PERF.md, PR 26).
#: At 0 (the usual zero-init) two thirds of the leaves have no gradient
#: at the first step. 0.1 keeps every leaf moving and the step
#: well-conditioned.
LAST_BN_SCALE = 0.1
_HI = lax.Precision.HIGHEST


def _block_names(stage_sizes):
    """[(flax-style block name, filters, stride, has_projection)]."""
    out, k = [], 0
    for i, count in enumerate(stage_sizes):
        for j in range(count):
            out.append(("BottleneckBlock_%d" % k, i,
                        2 if i > 0 and j == 0 else 1, j == 0))
            k += 1
    return out


def init_params(key, model):
    """The benchmark's weights, from the seed, in one traceable call
    (jit it): He-normal convolution kernels (fan-in), BatchNorm scale 1
    and bias 0 (``LAST_BN_SCALE`` for the last one of a block),
    LeCun-normal dense kernel, zero bias.
    Names follow the layout the program's ``ResNet`` module reads."""
    width, classes = model["width"], model["num_classes"]
    keys = iter(jax.random.split(key, 128))

    def conv(kh, cin, cout):
        std = (2.0 / (kh * kh * cin)) ** 0.5
        return {"kernel": std * jax.random.normal(
            next(keys), (kh, kh, cin, cout), jnp.float32)}

    def bn(c, scale=1.0):
        return {"scale": jnp.full((c,), scale, jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    params = {"conv_init": conv(7, 3, width), "bn_init": bn(width)}
    cin = width
    for name, stage, _, proj in _block_names(model["stage_sizes"]):
        f = width * 2 ** stage
        blk = {"Conv_0": conv(1, cin, f), "BatchNorm_0": bn(f),
               "Conv_1": conv(3, f, f), "BatchNorm_1": bn(f),
               "Conv_2": conv(1, f, 4 * f),
               "BatchNorm_2": bn(4 * f, LAST_BN_SCALE)}
        if proj:
            blk["Conv_3"] = conv(1, cin, 4 * f)
            blk["norm_proj"] = bn(4 * f)
        params[name] = blk
        cin = 4 * f
    params["Dense_0"] = {
        "kernel": cin ** -0.5 * jax.random.normal(
            next(keys), (cin, classes), jnp.float32),
        "bias": jnp.zeros((classes,), jnp.float32)}
    return params


def _rounder(precision):
    """What an operand of a multiplication is rounded to."""
    if precision == "float32":
        return lambda x: x
    dt = {"bfloat16": jnp.bfloat16, "float8": jnp.float8_e4m3fn}[precision]
    return lambda x: x.astype(dt).astype(jnp.float32)


def _conv(x, w, stride, padding, rnd):
    return lax.conv_general_dilated(
        rnd(x), rnd(w), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HI)


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _block(p, x, stride, rnd):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"], 1, "SAME", rnd),
                        p["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"]["kernel"], stride, "SAME",
                              rnd), p["BatchNorm_1"]))
    y = _bn(_conv(y, p["Conv_2"]["kernel"], 1, "SAME", rnd),
            p["BatchNorm_2"])
    if "Conv_3" in p:
        x = _bn(_conv(x, p["Conv_3"]["kernel"], stride, "SAME", rnd),
                p["norm_proj"])
    return jax.nn.relu(x + y)


def logits(params, images, model, precision="float32"):
    """[B, classes] for uint8/float ``images`` [B, H, W, 3], BatchNorm
    on the batch's own statistics (a training step's forward)."""
    rnd = _rounder(precision)
    x = images.astype(jnp.float32)
    x = _conv(x, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)], rnd)
    x = jax.nn.relu(_bn(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for name, _, stride, _ in _block_names(model["stage_sizes"]):
        x = jax.checkpoint(functools.partial(
            _block, stride=stride, rnd=rnd))(params[name], x)
    x = jnp.mean(x, axis=(1, 2))
    d = params["Dense_0"]
    return jnp.dot(rnd(x), rnd(d["kernel"]), precision=_HI) + d["bias"]


def loss_fn(params, images, labels, model, precision="float32"):
    """Mean softmax cross-entropy with integer labels."""
    z = logits(params, images, model, precision)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, labels[:, None].astype(jnp.int32), axis=1))


def leaf_norms(tree):
    """float32 vector of the L2 norm of every leaf, in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@functools.partial(jax.jit, static_argnames=("model_key", "precision"))
def _sgd_step(params, trace, images, labels, lr, momentum, model_key,
              precision):
    model = dict(model_key)
    model["stage_sizes"] = list(model["stage_sizes"])
    loss, grads = jax.value_and_grad(loss_fn)(params, images, labels,
                                              model, precision)
    trace = jax.tree.map(lambda g, t: g + momentum * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
    return params, trace, loss, leaf_norms(grads)


def follow(params, batches, model, optimizer, precision="float32",
           batch_rows=None):
    """Drive ``len(batches)`` SGD-with-momentum steps from ``params``
    over ``batches`` ([(images, labels)]); returns the readings the
    comparison uses: ``losses`` (one per step), ``grad_norms`` (per
    leaf, of the first step's gradient) and ``change_norms`` (per leaf,
    of parameters after the last step minus the first).

    ``batch_rows``: a fault for the tests only - keep just these rows
    of every batch and take the mean over them."""
    key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                       for k, v in model.items()))
    p0 = params
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for images, labels in batches:
        if batch_rows is not None:
            images, labels = images[batch_rows], labels[batch_rows]
        params, trace, loss, gnorm = _sgd_step(
            params, trace, jnp.asarray(images), jnp.asarray(labels),
            optimizer["learning_rate"], optimizer["momentum"], key,
            precision)
        losses.append(float(loss))
        if first is None:
            first = gnorm
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return {"losses": losses,
            "grad_norms": [float(v) for v in first],
            "change_norms": [float(v) for v in change]}
