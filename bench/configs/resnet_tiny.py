"""Plain reference of ResNet-Tiny, the MetaFed paper's client model.

Straightforward ``jax.numpy`` from the architecture's description: a 3x3
stem, three stages of basic blocks (two 3x3 convolutions with GroupNorm,
identity or strided 1x1 projection shortcut), global mean pool and a dense
head.  GroupNorm stands in for BatchNorm (statistics of non-IID clients do
not aggregate).  It shares no code with the program: the benchmark makes
the weights here and hands them to both.

Every convolution and matrix product runs at ``Precision.HIGHEST`` when
``dtype`` is float32, so on a TPU the reference is true float32 and not the
default single bfloat16 pass.  ``dtype=bfloat16`` computes everything in
bfloat16: the control that the comparison has to reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

GN_EPS = 1e-5


def _blocks(model: dict):
    """(name, c_in, c_out, stride) of every basic block, in order."""
    cin = model["widths"][0]
    for si, (w, d) in enumerate(zip(model["widths"], model["depths"])):
        for bi in range(d):
            yield f"s{si}b{bi}", cin, w, (2 if bi == 0 and si > 0 else 1)
            cin = w


def param_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    c0 = model["widths"][0]
    shapes = {"stem": (3, 3, model["in_channels"], c0), "stem_s": (c0,), "stem_b": (c0,)}
    for pre, cin, w, _ in _blocks(model):
        shapes[pre + "_c1"] = (3, 3, cin, w)
        shapes[pre + "_c2"] = (3, 3, w, w)
        for t in ("_s1", "_b1", "_s2", "_b2"):
            shapes[pre + t] = (w,)
        if cin != w:
            shapes[pre + "_proj"] = (1, 1, cin, w)
    shapes["head_w"] = (model["widths"][-1], model["num_classes"])
    shapes["head_b"] = (model["num_classes"],)
    return shapes


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, shape_items):
    params = {}
    for i, (name, shape) in enumerate(shape_items):
        k = jax.random.fold_in(key, i)
        if name.endswith(("_s", "_s1", "_s2")):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(("_b", "_b1", "_b2", "head_b")):
            params[name] = jnp.zeros(shape, jnp.float32)
        elif name == "head_w":
            params[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
        else:  # He-normal convolution kernels, HWIO
            fan_in = shape[0] * shape[1] * shape[2]
            params[name] = (2.0 / fan_in) ** 0.5 * jax.random.normal(k, shape, jnp.float32)
    return params


def init_params(model: dict, seed: int) -> dict:
    """Float32 weights on the device, in one jitted call from ``seed``."""
    return _init(jax.random.PRNGKey(seed), tuple(sorted(param_shapes(model).items())))


def param_count(model: dict) -> int:
    total = 0
    for shape in param_shapes(model).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def _precision(dtype):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _group_norm(x, scale, bias, groups):
    B, H, W, C = x.shape
    g = min(groups, C)
    xg = x.reshape(B, H, W, g, C // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mean) / jnp.sqrt(var + GN_EPS)).reshape(B, H, W, C)
    return xn * scale.astype(x.dtype) + bias.astype(x.dtype)


def forward(params: dict, model: dict, images, dtype=jnp.float32):
    """(B, H, W, C) images -> (B, num_classes) logits, computed in ``dtype``."""
    prec = _precision(dtype)
    groups = model["groups"]
    x = _conv(images.astype(dtype), params["stem"], 1, prec)
    x = jax.nn.relu(_group_norm(x, params["stem_s"], params["stem_b"], groups))
    for pre, cin, w, stride in _blocks(model):
        h = _conv(x, params[pre + "_c1"], stride, prec)
        h = jax.nn.relu(_group_norm(h, params[pre + "_s1"], params[pre + "_b1"], groups))
        h = _conv(h, params[pre + "_c2"], 1, prec)
        h = _group_norm(h, params[pre + "_s2"], params[pre + "_b2"], groups)
        shortcut = _conv(x, params[pre + "_proj"], stride, prec) if cin != w else x
        x = jax.nn.relu(h + shortcut)
    pooled = jnp.mean(x, axis=(1, 2))
    return jnp.dot(pooled, params["head_w"].astype(dtype), precision=prec) + params["head_b"].astype(dtype)


def loss(params: dict, model: dict, batch: dict, dtype=jnp.float32):
    """Mean softmax cross-entropy of one batch of ``"image"`` and ``"label"``."""
    logits = forward(params, model, batch["image"], dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["label"][:, None], axis=1))


def forward_flops(model: dict, image_shape) -> int:
    """Convolution and dense FLOPs of one sample's forward pass (2 per
    multiply-add), from the shapes alone.  Normalization, activations and
    pooling are left out: they are a rounding error beside the convolutions."""
    H, W, _ = image_shape
    c0 = model["widths"][0]
    flops = 2 * H * W * 9 * model["in_channels"] * c0
    h, w_ = H, W
    for _pre, cin, cout, stride in _blocks(model):
        ho, wo = -(-h // stride), -(-w_ // stride)
        flops += 2 * ho * wo * 9 * cin * cout + 2 * ho * wo * 9 * cout * cout
        if cin != cout:
            flops += 2 * ho * wo * cin * cout
        h, w_ = ho, wo
    return flops + 2 * model["widths"][-1] * model["num_classes"]


def train_flops_per_sample(model: dict, dataset: dict) -> int:
    """Forward and backward of one image of the configuration's ``dataset``:
    three times the forward (the backward pass takes one product for the
    input gradient and one for the weights')."""
    return 3 * forward_flops(model, dataset["shape"])
