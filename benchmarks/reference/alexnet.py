"""Plain reference of the ``convnet`` family (AlexNet as
``veles_tpu/samples/alexnet.py`` ships it): forward pass, softmax loss,
gradients and the momentum-SGD update in straightforward ``jax.numpy``
at float32 / ``highest``.  No kernels, no workflow, nothing imported from
the program and nothing taken from it: the weights are made here from
the seed and GIVEN to the program.

Departures from Krizhevsky 2012, all the reference platform's own: one
tower (no grouped convolutions), 227x227 input, weight decay on weights
only, gaussian weights with the stated deviations; biases here are
uniform in +-1/sqrt(fan-in) (the benchmark's choice: the platform's
default filling, made from the benchmark's seed).

``quant="fp8"`` is the CONTROL, the nearest precision below the
configuration's bfloat16, as a careful fp8 recipe would do it: both
operands of every convolution and matrix product rounded to e4m3
(activations with one scale per tensor, weights one per output channel),
their cotangents to e5m2 with one scale per tensor.  (A plain cast with
no scale underflows every cotangent to zero: that is the fault "state
unchanged", not a precision, and is not kept.)  The benchmark's own runs
never run it.

The jitted step takes the dropout seeds as ARGUMENTS: closed over, they
would be constants of the program and every ``--seed`` would compile
the float32 step anew (34 s on the v5e against 1.4 s of work).
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def layer_shapes(config):
    """``[(index, kind, w shape, b shape, fan_in, w_std)]`` of the
    weighted layers, walking the stack's spatial sizes."""
    h, w, c = config["input_shape"]
    flat = None
    out = []
    for index, layer in enumerate(config["layers"]):
        kind = layer["type"]
        if kind.startswith("conv"):
            k = layer["kernels"]
            fan_in = layer["ky"] * layer["kx"] * c
            out.append((index, "conv", (layer["ky"], layer["kx"], c, k),
                        (k,), fan_in, layer["w_std"]))
            h = (h + 2 * layer["pad"] - layer["ky"]) // layer["stride"] + 1
            w = (w + 2 * layer["pad"] - layer["kx"]) // layer["stride"] + 1
            c = k
        elif kind == "max_pooling":
            h = (h - layer["ky"]) // layer["stride"] + 1
            w = (w - layer["kx"]) // layer["stride"] + 1
        elif kind.startswith("all2all") or kind == "softmax":
            fan_in = flat if flat is not None else h * w * c
            out.append((index, "dense", (fan_in, layer["out"]),
                        (layer["out"],), fan_in, layer["w_std"]))
            flat = layer["out"]
    return out


def init_params(config, seed):
    """``{index: {"w", "b"}}`` in float32, drawn on the device in ONE
    jitted call from the seed."""
    shapes = layer_shapes(config)

    @jax.jit
    def make(key):
        params = {}
        for index, _kind, w_shape, b_shape, fan_in, w_std in shapes:
            kw, kb = jax.random.split(jax.random.fold_in(key, index))
            bound = 1.0 / math.sqrt(fan_in)
            params[index] = {
                "w": jax.random.normal(kw, w_shape, jnp.float32) * w_std,
                "b": jax.random.uniform(kb, b_shape, jnp.float32,
                                        -bound, bound)}
        return params

    return make(jax.random.key(int(seed)))


def dropout_seeds(config, seed):
    """One 30-bit mask-stream seed per dropout layer, from the seed."""
    import numpy
    rng = numpy.random.default_rng([int(seed), 0xD50])
    return {index: int(rng.integers(0, 2 ** 30))
            for index, layer in enumerate(config["layers"])
            if layer["type"] == "dropout"}


def _round_fp8(x, dtype, top, axes):
    """``x`` through an fp8 type and back, its largest magnitude (over
    ``axes``) first brought to the type's ``top``."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


def _fake_fp8(x, axes):
    q = _round_fp8(x, jnp.float8_e4m3fn, 448.0, axes)
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_cotangent(x):
    """Identity whose cotangent is rounded to e5m2, so that the backward
    products of the control take 8-bit operands as its forward products
    do."""
    return x


_fp8_cotangent.defvjp(
    lambda x: (x, None),
    lambda _, g: (_round_fp8(g, jnp.float8_e5m2, 57344.0, None),))


def _operands(x, w, quant):
    if quant is None:
        return x, w
    if quant != "fp8":
        raise ValueError("unknown control precision %r" % quant)
    return _fake_fp8(x, None), _fake_fp8(w, tuple(range(w.ndim - 1)))


def _product_out(y, quant):
    return y if quant is None else _fp8_cotangent(y)


def forward_loss(params, images, labels, config, masks_seed, step,
                 quant=None):
    """Mean softmax cross-entropy of one minibatch.  ``images`` are the
    gathered uint8 rows; normalisation is the configuration's scale."""
    x = images.astype(jnp.float32) * config["normalization"]["scale"]
    for index, layer in enumerate(config["layers"]):
        kind = layer["type"]
        if kind.startswith("conv"):
            xq, wq = _operands(x, params[index]["w"], quant)
            x = jax.lax.conv_general_dilated(
                xq, wq, (layer["stride"],) * 2,
                ((layer["pad"],) * 2,) * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=HIGHEST)
            x = _product_out(x, quant) + params[index]["b"]
            x = jnp.maximum(x, 0.0)
        elif kind == "lrn":
            half = layer["n"] // 2
            sq = jnp.pad(x * x, ((0, 0),) * 3
                         + ((half, layer["n"] - 1 - half),))
            window = sum(sq[..., i:i + x.shape[-1]]
                         for i in range(layer["n"]))
            x = x / (layer["k"] + layer["alpha"] * window) ** layer["beta"]
        elif kind == "max_pooling":
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max,
                (1, layer["ky"], layer["kx"], 1),
                (1, layer["stride"], layer["stride"], 1), "VALID")
        elif kind == "dropout":
            keep = 1.0 - layer["ratio"]
            key = jax.random.key(
                ((masks_seed[index] + step) & 0x3fffffff).astype(
                    jnp.uint32))
            mask = jax.random.bernoulli(key, keep, x.shape)
            x = jnp.where(mask, x / keep, 0.0)
        else:
            x = x.reshape(x.shape[0], -1)
            xq, wq = _operands(x, params[index]["w"], quant)
            x = _product_out(jnp.dot(xq, wq, precision=HIGHEST),
                             quant) + params[index]["b"]
            if kind != "softmax":
                x = jnp.maximum(x, 0.0)
    logp = jax.nn.log_softmax(x, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -picked.mean()


@functools.lru_cache(maxsize=None)
def _step_program(config_json, quant, half_batch):
    """The jitted momentum-SGD step of one configuration: one program
    for every seed (the seed's numbers are all arguments)."""
    config = json.loads(config_json)
    solver = config["solver"]
    lr, moment = solver["learning_rate"], solver["gradient_moment"]
    decay = solver["weights_decay"]

    @jax.jit
    def step(state, velocity, images, labels, seeds, index):
        if half_batch:
            images = images[: images.shape[0] // 2]
            labels = labels[: labels.shape[0] // 2]
        loss, grads = jax.value_and_grad(forward_loss)(
            state, images, labels, config, seeds, index, quant)
        new_state, new_velocity = {}, {}
        for layer, leaves in state.items():
            new_state[layer], new_velocity[layer] = {}, {}
            for name, value in leaves.items():
                reg = decay * value if name == "w" else 0.0
                v = moment * velocity[layer][name] \
                    - lr * (grads[layer][name] + reg)
                new_velocity[layer][name] = v
                new_state[layer][name] = value + v
        return new_state, new_velocity, loss, grads

    return step


def leaves_of(tree):
    """``{"<layer index>.w" / ".b": array}`` of a ``{layer: {name:
    array}}`` tree."""
    return {"%d.%s" % (layer, name): value
            for layer, leaves in tree.items()
            for name, value in leaves.items()}


def norm(x):
    return float(jnp.sqrt(jnp.sum(jnp.square(x))))


def train_steps(config, params, batches, masks_seed, quant=None,
                half_batch=False):
    """Follow the first ``len(batches)`` steps.  ``batches`` is a list of
    ``(uint8 images, int32 labels)``.  Returns ``{"losses": [...],
    "grad": {leaf: step 1's gradient}, "grad_norm": {leaf: its norm},
    "delta_norm": {leaf: norm of the parameters' change after the last
    step}}`` with leaves named ``"<layer index>.w"`` / ``".b"``.

    ``half_batch=True`` plants the fault "half of the batch left out,
    the mean taken over the rest"."""
    step = _step_program(json.dumps(config, sort_keys=True), quant,
                         bool(half_batch))
    # config keys are strings in JSON; the seeds' keys stay the layers'
    # indices, as ``forward_loss`` looks them up
    seeds = {int(k): jnp.int32(v) for k, v in masks_seed.items()}
    state = params
    velocity = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    for index, (images, labels) in enumerate(batches):
        state, velocity, loss, grads = step(
            state, velocity, images, labels, seeds, jnp.int32(index))
        losses.append(float(loss))
        if index == 0:
            grad = leaves_of(grads)
    delta = leaves_of(jax.tree.map(lambda a, b: a - b, state, params))
    return {"losses": losses, "grad": grad,
            "grad_norm": {k: norm(v) for k, v in grad.items()},
            "delta_norm": {k: norm(v) for k, v in delta.items()}}
