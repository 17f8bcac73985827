"""Required operations and bytes, from shapes alone.

The yardstick's arithmetic: what the ALGORITHM needs, whatever kernel
implements it.  Only matrix-multiply and convolution work is counted
(2 operations per multiply-add); normalisation, pooling, activation and
softmax work is left out, so a share of the peak computed from these
numbers can only be read too low, never too high.  Nothing here imports
the program.
"""


def conv_out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def _weighted_layers(config):
    """``(layer index, fan-in of one output, output channels or units,
    output positions)`` of every weighted layer of a ``family: convnet``
    configuration, walking the stack's spatial sizes."""
    h, w, c = config["input_shape"]
    flat = None
    for index, layer in enumerate(config["layers"]):
        kind = layer["type"]
        if kind.startswith("conv"):
            h = conv_out(h, layer["ky"], layer["stride"], layer["pad"])
            w = conv_out(w, layer["kx"], layer["stride"], layer["pad"])
            yield index, layer["ky"] * layer["kx"] * c, \
                layer["kernels"], h * w
            c = layer["kernels"]
        elif kind == "max_pooling":
            h = conv_out(h, layer["ky"], layer["stride"], 0)
            w = conv_out(w, layer["kx"], layer["stride"], 0)
        elif kind.startswith("all2all") or kind == "softmax":
            yield index, flat if flat is not None else h * w * c, \
                layer["out"], 1
            flat = layer["out"]
        elif kind not in ("lrn", "dropout"):
            raise ValueError("flops.py knows no layer type %r" % kind)


def convnet_macs(config):
    """Per-image multiply-adds of every weighted layer:
    ``[(layer index, macs)]``."""
    return [(index, fan_in * units * positions)
            for index, fan_in, units, positions in _weighted_layers(config)]


def convnet_train_flops_per_image(config):
    """Forward + backward of one image: each weighted layer costs its
    forward once more for the weight gradient and once more for the
    input gradient; the first layer needs no input gradient."""
    macs = convnet_macs(config)
    forward = sum(2 * m for _i, m in macs)
    return 3 * forward - 2 * macs[0][1]


def convnet_param_count(config):
    """Weights + biases of a convnet configuration."""
    return sum(fan_in * units + units
               for _i, fan_in, units, _p in _weighted_layers(config))


# -- GPT-2 family -----------------------------------------------------------

def gpt_block_matmul_params(config):
    """Matmul weights of ONE block: wqkv d*3d, wo d*d, w1 d*f, w2 f*d."""
    d, f = config["n_embd"], config["n_inner"]
    return 4 * d * d + 2 * d * f


def gpt_readout_params(config):
    return config["vocab_size"] * config["n_embd"]


def gpt_param_count(config):
    d, f, L = config["n_embd"], config["n_inner"], config["n_layer"]
    block = gpt_block_matmul_params(config) + f + d + 4 * d
    return (config["vocab_size"] * d + config["n_positions"] * d
            + L * block + 2 * d)


def gpt_prefill_flops(config, n):
    """A prompt of ``n`` real tokens: every block over every token,
    causal attention (each query against the keys up to itself), and the
    readout at the ONE position whose logits are needed."""
    d, L = config["n_embd"], config["n_layer"]
    blocks = 2 * L * gpt_block_matmul_params(config) * n
    attention = L * 4 * d * (n * (n + 1) // 2)
    return blocks + attention + 2 * gpt_readout_params(config)


def gpt_decode_flops(config, live):
    """One output token whose query sees ``live`` cached positions
    (itself included)."""
    d, L = config["n_embd"], config["n_layer"]
    return (2 * L * gpt_block_matmul_params(config)
            + 2 * gpt_readout_params(config) + L * 4 * d * live)


def gpt_weight_bytes(config, itemsize=2):
    """What one decode step has to read of the weights: every block and
    the tied embedding (as the readout), once."""
    d, f, L = config["n_embd"], config["n_inner"], config["n_layer"]
    block = gpt_block_matmul_params(config) + f + d + 4 * d
    return (L * block + gpt_readout_params(config) + 2 * d) * itemsize


def gpt_kv_bytes(config, live, itemsize=2):
    """Keys and values of ``live`` cached positions, all layers."""
    return 2 * config["n_layer"] * config["n_embd"] * live * itemsize
