"""ResNet9 in plain ``jax.numpy`` float32: the reference of the
``resnet9-cifar10`` configuration.

Written from the description of the network (Page's DAWNBench
``cifar10_fast`` ResNet9 as CommEfficient's ``models/resnet9.py`` ships
it, BatchNorm off as in the FetchSGD CIFAR10 runs): NHWC, 3x3
convolutions with padding 1 and no bias, ReLU, 2x2 max-pooling,

    prep   conv 3->64
    layer1 conv 64->128, pool;  residual: x + relu(conv(relu(conv(x))))
    layer2 conv 128->256, pool
    layer3 conv 256->512, pool; residual as above
    pool 2x2, flatten (2*2*512 = 2048), linear 2048->10 without bias,
    logits scaled by 0.125

6,584,000 parameters. Noted departure from Page's net: the head pools
the final 4x4 map by 2x2, not 4x4, so the linear layer is 2048 wide;
that is the net the source's repo trains and the one sized here.

No flax, no kernel, nothing of the program. The parameter tree uses the
names flax gives the program's module so that the builder can hand the
same weights to both; the builder checks names and shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: clients differentiated together by ``lib/fetchsgd_ref.follow``
CLIENTS_PER_BLOCK = 10

# Limits of ``correct`` for this configuration, each set from readings
# on the chip (PERF.md section 2 has the table of readings):
#   sound   = the program (bf16 compute, as the configuration states)
#             against this float32 reference, largest over the seeds;
#   control = this reference computed in fp8 (e4m3, per-tensor scale,
#             straight-through: ``lib/fetchsgd_ref.quantizer``) against
#             itself in float32, smallest over the seeds.
# grad_rel_l2 is the number the lower precision has to fail (sound
# 0.0137 at most over 22 seeds, control 0.0614 at least over 9: the
# limit has a factor of two on either side). The other three are held
# at about three times the sound runs' largest (loss_gap 0.0062,
# grad_norm_gap 0.0030, delta_norm_gap 0.0104), against the faults
# named beside them; they do not tell fp8 from bf16 and need not.
LIMITS = {
    "loss_gap": 0.02,        # a part of the batch left out
    "grad_norm_gap": 0.009,   # a gradient scaled or partly dropped
    "grad_rel_l2": 0.028,     # computing below bf16
    "delta_norm_gap": 0.027,  # a step that returns its state unchanged
}

_PLAN = (("ConvBN_0", None), ("ConvBN_1", None), ("Residual_0", 2),
         ("ConvBN_2", None), ("ConvBN_3", None), ("Residual_1", 2))


def channel_plan(spec):
    ch = spec.get("channels") or {"prep": 64, "layer1": 128,
                                  "layer2": 256, "layer3": 512}
    return [ch["prep"], ch["layer1"], ch["layer2"], ch["layer3"]]


def _conv_shapes(spec):
    c0, c1, c2, c3 = channel_plan(spec)
    cin = int(spec.get("initial_channels", 3))
    return {"ConvBN_0": (cin, c0), "ConvBN_1": (c0, c1),
            "Residual_0": (c1, c1), "ConvBN_2": (c1, c2),
            "ConvBN_3": (c2, c3), "Residual_1": (c3, c3)}


def init_params(key, spec):
    """He-normal weights (std sqrt(2 / fan_in)) from ``key``, float32,
    in one traced call."""
    shapes = _conv_shapes(spec)
    c3 = channel_plan(spec)[3]
    n_cls = int(spec.get("num_classes", 10))

    def he(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) \
            * jnp.sqrt(2.0 / fan_in)

    keys = iter(jax.random.split(key, 16))
    params = {}
    for name, depth in _PLAN:
        cin, cout = shapes[name]
        if depth is None:
            params[name] = {"Conv_0": {"kernel": he(
                next(keys), (3, 3, cin, cout), 9 * cin)}}
        else:
            params[name] = {
                f"ConvBN_{i}": {"Conv_0": {"kernel": he(
                    next(keys), (3, 3, cin, cout), 9 * cin)}}
                for i in range(depth)}
    params["Dense_0"] = {"kernel": he(next(keys), (4 * c3, n_cls), 4 * c3)}
    return params


def _conv(x, w, q):
    return jax.lax.conv_general_dilated(
        q(x), q(w), window_strides=(1, 1), padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _pool(x):
    n, h, w, c = x.shape
    return jnp.max(x.reshape(n, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def logits(params, x, q=lambda a: a):
    def cb(name, x, pool=False):
        y = jax.nn.relu(_conv(x, params[name]["Conv_0"]["kernel"], q))
        return _pool(y) if pool else y

    def res(name, x):
        y = x
        for i in range(2):
            y = jax.nn.relu(_conv(
                y, params[name][f"ConvBN_{i}"]["Conv_0"]["kernel"], q))
        return x + y   # relu(relu(.)) == relu(.)

    x = x.astype(jnp.float32)
    x = cb("ConvBN_0", x)
    x = res("Residual_0", cb("ConvBN_1", x, pool=True))
    x = cb("ConvBN_2", x, pool=True)
    x = res("Residual_1", cb("ConvBN_3", x, pool=True))
    x = _pool(x).reshape(x.shape[0], -1)
    return 0.125 * (q(x) @ q(params["Dense_0"]["kernel"]))


def client_loss(params, b, spec, q=lambda a: a):
    """One client's masked-mean cross-entropy. ``b``: x (B, 32, 32, 3),
    y (B,), mask (B,)."""
    logp = jax.nn.log_softmax(logits(params, b["x"], q))
    nll = -jnp.take_along_axis(logp, b["y"][:, None].astype(jnp.int32),
                               axis=1)[:, 0]
    return jnp.sum(nll * b["mask"]) / jnp.maximum(jnp.sum(b["mask"]), 1.0)


def train_flops_per_round(spec, cell):
    """FLOPs the forward and backward passes of one round need: per
    image 2 * MACs forward, twice that backward (no recomputation
    counted), times the images of a round. ResNet9 at 32x32: 379.1M
    MACs an image -> 2.27 GFLOP."""
    c0, c1, c2, c3 = channel_plan(spec)
    cin = int(spec.get("initial_channels", 3))
    n_cls = int(spec.get("num_classes", 10))
    macs = (32 * 32 * 9 * cin * c0
            + 32 * 32 * 9 * c0 * c1
            + 2 * 16 * 16 * 9 * c1 * c1
            + 16 * 16 * 9 * c1 * c2
            + 8 * 8 * 9 * c2 * c3
            + 2 * 4 * 4 * 9 * c3 * c3
            + 4 * c3 * n_cls)
    images = cell["clients_per_round"] * cell["local_batch_size"]
    return 3 * 2 * macs * images
