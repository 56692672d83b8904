"""Operations a ResNet v1.5 training step needs, from shapes alone
(never from XLA's cost analysis, which counts what XLA chose to do).

A multiply-accumulate is 2 FLOPs. The backward pass of a convolution
or dense layer needs two more products of the forward's size (the
gradient of the weights and of the input); the first convolution's
input gradient is never needed. BatchNorm, ReLU, pooling and the loss
are left out (under 1% of the total and bound by bandwidth).
"""


def _same(size, stride):
    return -(-size // stride)


def conv_layers(model, image):
    """[(name, out_hw, kernel, cin, cout)] of every convolution, in order."""
    width = model["width"]
    layers = []
    hw = _same(image, 2)
    layers.append(("conv_init", hw, 7, 3, width))
    hw = _same(hw, 2)  # 3x3 max pool, stride 2
    cin, k = width, 0
    for i, count in enumerate(model["stage_sizes"]):
        f = width * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            name = "BottleneckBlock_%d" % k
            layers.append((name + "/Conv_0", hw, 1, cin, f))
            out = _same(hw, stride)
            layers.append((name + "/Conv_1", out, 3, f, f))
            layers.append((name + "/Conv_2", out, 1, f, 4 * f))
            if j == 0:
                layers.append((name + "/Conv_3", out, 1, cin, 4 * f))
            hw, cin, k = out, 4 * f, k + 1
    return layers


def forward_macs_per_image(model, image):
    macs = sum(hw * hw * k * k * cin * cout
               for _, hw, k, cin, cout in conv_layers(model, image))
    final = model["width"] * 2 ** (len(model["stage_sizes"]) - 1) * 4
    return macs + final * model["num_classes"]


def train_flops_per_image(model, image):
    """Forward plus backward, the first convolution's input gradient
    left out."""
    _, hw, k, cin, cout = conv_layers(model, image)[0]
    first = hw * hw * k * k * cin * cout
    return 2 * (3 * forward_macs_per_image(model, image) - first)


def parameter_count(model):
    n = 0
    for name, _, k, cin, cout in conv_layers(model, 224):
        n += k * k * cin * cout + 2 * cout  # kernel + BatchNorm scale, bias
    final = model["width"] * 2 ** (len(model["stage_sizes"]) - 1) * 4
    return n + final * model["num_classes"] + model["num_classes"]
