"""Plain VGG forward pass (Simonyan & Zisserman, arXiv:1409.1556).

3x3 'SAME' convolutions with bias and ReLU, 2x2 max-pooling with stride
2, flatten in (H, W, C) order, dense layers with ReLU except the last,
which gives the logits.  ``precision`` is handed to every convolution and
matrix product: "highest" is float32 on the TPU.  "bf16_3x" is the
control's precision, three bfloat16 passes: XLA's "high" on the TPU.  A
backend that ignores the precision (the CPU) gets it written out: each
operand is split into a bfloat16 head and a bfloat16 tail, and the
products head*head + head*tail + tail*head are summed in float32, as one
product over operands stacked along the summed axis.  (On the TPU that
form read a hundred times farther from float32 than three passes should,
so the chip uses XLA's own.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _conv(x, w, precision=None, out=None):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision, preferred_element_type=out)


def _dot(x, w, precision=None, out=None):
    return jnp.dot(x, w, precision=precision, preferred_element_type=out)


def _split(a):
    head = a.astype(jnp.bfloat16)
    return head, (a - head.astype(jnp.float32)).astype(jnp.bfloat16)


def _apply(op, x, w, precision: str):
    if precision != "bf16_3x":
        return op(x, w, precision)
    if jax.default_backend() == "tpu":
        return op(x, w, "high")
    (xh, xt), (wh, wt) = _split(x), _split(w)
    # x's last axis and w's second to last are the summed (input) axis
    xs = jnp.concatenate([xh, xh, xt], axis=-1)
    ws = jnp.concatenate([wh, wt, wh], axis=-2)
    return op(xs, ws, out=jnp.float32)


def forward(cfg: dict, params: list, x, precision: str = "highest"):
    """Logits ``(B, classes)`` of images ``x`` ``(B, H, W, C)``."""
    n_layers = len(cfg["layers"])
    for i, ((kind, *_), p) in enumerate(zip(cfg["layers"], params)):
        if kind == "conv":
            x = jax.nn.relu(_apply(_conv, x, p["w"], precision) + p["b"])
        elif kind == "pool":
            s = cfg["pool"]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, s, s, 1), (1, s, s, 1), "VALID")
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        else:
            x = _apply(_dot, x, p["w"], precision) + p["b"]
            if i < n_layers - 1:
                x = jax.nn.relu(x)
    return x


def logits(cfg: dict, params: list, images, precision: str = "highest"):
    """One jitted forward over all images (batch-size images)."""
    return jax.jit(forward, static_argnums=(0, 3))(
        _frozen(cfg), params, images, precision)


class _frozen(dict):
    """A hashable view of the configuration, for ``static_argnums``."""

    def __hash__(self):
        return hash(repr(sorted((k, repr(v)) for k, v in self.items())))
