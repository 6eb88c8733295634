"""Plain reference of the `resnet50_imagenet` configuration: ResNet-50 v1
(He et al. 2015, arXiv:1512.03385, Table 1, 50-layer column) — forward,
softmax cross-entropy loss and gradients by `jax.grad`, in float32
`jax.numpy` at "highest" matmul precision.  No AMP, no fusion tricks, no
program code: the benchmark's drivers hold the program to this.

Structure as published: 7x7/2 conv (64) - BN - ReLU - 3x3/2 max pool -
bottleneck stages [3, 4, 6, 3] of (1x1, 3x3, 1x1 x4) convs, each followed by
BN, ReLU after the first two and after the residual sum, projection
shortcuts (1x1 conv + BN) where the shape changes - global average pool -
fc(1000) - softmax.

Departures from the paper, all inherited from the configuration under test
(`paddle_tpu.models.resnet`), so that both sides compute the same function:
  * the stride of a down-sampling block sits on its 3x3 conv (the "v1.5"
    placement of the Fluid benchmark model), not on its first 1x1;
  * BN uses the batch's own statistics (training mode), biased variance,
    eps 1e-5; convs have no bias;
  * filters are stored OIHW, activations run NHWC.
Weight decay is not part of the loss here: the driver adds `decay * p` to
the gradient, as the program's regularizer does.
"""

import jax
import jax.numpy as jnp

STAGES = (3, 4, 6, 3)
EPS = 1e-5


def split_params(ordered):
    """The program's trainable parameters in creation order — (conv filter,
    BN scale, BN bias) per conv layer, then (fc weight, fc bias) — as the
    pytree `forward` walks."""
    ordered = list(ordered)
    body, (fc_w, fc_b) = ordered[:-2], ordered[-2:]
    if len(body) % 3:
        raise ValueError("%d parameters before the fc layer" % len(body))
    return {"convs": [tuple(body[i:i + 3]) for i in range(0, len(body), 3)],
            "fc_w": fc_w, "fc_b": fc_b}


def _conv_bn(x, layer, stride, pad, relu):
    w, scale, bias = layer
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = (y - mean) / jnp.sqrt(var + EPS) * scale + bias
    return jnp.maximum(y, 0.0) if relu else y


def forward(params, images, stages=STAGES):
    """images [B, H, W, 3] float32 -> logits [B, classes]."""
    layers = iter(params["convs"])
    x = _conv_bn(images.astype(jnp.float32), next(layers), 2, 3, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    for stage, count in enumerate(stages):
        width = 64 * 2 ** stage
        for i in range(count):
            stride = 2 if (i == 0 and stage > 0) else 1
            y = _conv_bn(x, next(layers), 1, 0, True)
            y = _conv_bn(y, next(layers), stride, 1, True)
            y = _conv_bn(y, next(layers), 1, 0, False)
            if x.shape[-1] != width * 4 or stride != 1:
                x = _conv_bn(x, next(layers), stride, 0, False)
            x = jnp.maximum(x + y, 0.0)
    if next(layers, None) is not None:
        raise ValueError("parameters left over after the last stage")
    x = jnp.mean(x, axis=(1, 2))
    return jnp.matmul(x, params["fc_w"],
                      precision=jax.lax.Precision.HIGHEST) + params["fc_b"]


def loss(params, images, labels, stages=STAGES):
    """Mean softmax cross-entropy; labels [B] or [B, 1] integer."""
    logp = jax.nn.log_softmax(forward(params, images, stages), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.reshape(-1, 1).astype(jnp.int32), axis=-1)
    return -jnp.mean(picked)


def loss_and_grads(params, images, labels, stages=STAGES):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, images, labels, stages)
