"""Pure-JAX policy/value networks (init/apply pairs, pytree params).

Both init and apply must stay pure and shape-static: the fleet trainer
(repro.train.fused.fleet) vmaps the ENTIRE training loop — `*_init`
included, over a traced-key axis — so a fleet of F experiments owns one
(F, ...)-batched params pytree. Anything host-dependent here (python
randomness, data-dependent shapes) would break that batching.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

Activation = {
    "elu": jax.nn.elu,
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
    "gelu": jax.nn.gelu,
}

CONV_SPECS = [  # (kernel_h, kernel_w, stride) per conv layer
    (8, 8, 4),
    (4, 4, 2),
]


def mlp_init(key, sizes: Sequence[int], dtype=jnp.float32):
    params = []
    for i in range(len(sizes) - 1):
        key, sub = jax.random.split(key)
        fan_in = sizes[i]
        w = jax.random.normal(sub, (sizes[i], sizes[i + 1]), dtype) * jnp.sqrt(2.0 / fan_in)
        b = jnp.zeros((sizes[i + 1],), dtype)
        params.append({"w": w, "b": b})
    return params


def mlp_apply(params, x, activation: str = "elu"):
    """The MLP in float32. The dots ask for HIGHEST precision: at DEFAULT a
    TPU computes a float32 matmul in one bfloat16 pass, below the float32
    the learner states. Other backends compute float32 dots either way."""
    act = Activation[activation]
    for i, layer in enumerate(params):
        x = jnp.matmul(x, layer["w"],
                       precision=jax.lax.Precision.HIGHEST) + layer["b"]
        if i < len(params) - 1:
            x = act(x)
    return x


def cnn_init(key, in_shape: Tuple[int, ...], channels=(16, 32), dense=256, out=2, dtype=jnp.float32):
    """Nature-DQN-lite conv net.

    in_shape: (H, W) single grayscale frames, or (N, H, W) for stacked
    frames (core.wrappers.FrameStack) — the stack axis becomes the N input
    channels, the classic Atari-DQN pipeline.
    """
    if len(in_shape) == 2:
        cin, (h, w) = 1, in_shape
    elif len(in_shape) == 3:
        cin, h, w = in_shape
        if cin == 1:
            # cnn_apply infers the layout from the conv fan-in, and cin == 1
            # is indistinguishable from unstacked (H, W) frames at apply
            # time — a 1-frame stack would silently fold into the batch.
            raise ValueError("1-frame stacks are ambiguous: use in_shape="
                             "(H, W) (drop the FrameStack) or >= 2 frames")
    else:
        raise ValueError(f"cnn obs must be (H, W) or (N, H, W); got {in_shape}")
    params = {"convs": [], "dense": None, "out": None}
    for (kh, kw, s), cout in zip(CONV_SPECS, channels):
        key, sub = jax.random.split(key)
        fan_in = kh * kw * cin
        # Strides stay in the static CONV_SPECS table, NOT in the params
        # pytree: a non-array leaf would be traced when the params ride a
        # scan carry (train_compiled) and conv strides must be static.
        params["convs"].append({
            "w": jax.random.normal(sub, (kh, kw, cin, cout), dtype) * jnp.sqrt(2.0 / fan_in),
            "b": jnp.zeros((cout,), dtype),
        })
        h = (h - kh) // s + 1
        w = (w - kw) // s + 1
        cin = cout
    flat = h * w * cin
    key, k1, k2 = jax.random.split(key, 3)
    params["dense"] = {
        "w": jax.random.normal(k1, (flat, dense), dtype) * jnp.sqrt(2.0 / flat),
        "b": jnp.zeros((dense,), dtype),
    }
    params["out"] = {
        "w": jax.random.normal(k2, (dense, out), dtype) * jnp.sqrt(2.0 / dense),
        "b": jnp.zeros((out,), dtype),
    }
    return params


def cnn_apply(params, x, activation: str = "elu"):
    """x: (..., H, W) grayscale or (..., N, H, W) stacked frames -> (..., out).

    The input layout is recovered from the first conv's fan-in: cin == 1
    means plain (H, W) frames, cin > 1 means an N-frame stack whose leading
    axis maps to input channels.
    """
    act = Activation[activation]
    cin = params["convs"][0]["w"].shape[2]
    nd = 2 if cin == 1 else 3
    batch_shape = x.shape[:-nd]
    if cin == 1:
        x = x.reshape((-1,) + x.shape[-2:])[..., None]        # (B, H, W, 1)
    else:
        x = x.reshape((-1,) + x.shape[-3:])
        x = jnp.moveaxis(x, 1, -1)                            # (B, H, W, N)
    for conv, (_, _, s) in zip(params["convs"], CONV_SPECS):
        x = jax.lax.conv_general_dilated(
            x, conv["w"], (s, s), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + conv["b"]
        x = act(x)
    x = x.reshape(x.shape[0], -1)
    x = act(x @ params["dense"]["w"] + params["dense"]["b"])
    x = x @ params["out"]["w"] + params["out"]["b"]
    return x.reshape(batch_shape + (x.shape[-1],))
