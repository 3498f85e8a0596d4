"""The paper's own evaluation models (Section V):

* MLR — multinomial logistic regression on flattened 28x28 images (convex).
* CNN — 5x5x32 conv > 2x2 maxpool > 5x5x64 conv > 2x2 maxpool >
  FC(3136->512) > FC(512->10); 1,663,370 parameters, matching the paper's
  stated total (its "1024x512" FC is a typo — 7*7*64=3136 inputs).

The CNN also has a client-batched form, `cnn_apply_clients`, for local
training of K clients at once (`CLIENTS_APPLY`). Its params are K
stacked CNNs: every leaf of `cnn_init`'s tree with a leading K axis.
Its input is batch-major, (B, K, 28, 28, 1), and its logits (B, K, 10),
so `classification_loss` reads each client's minibatch on axis 0 as it
does for `cnn_apply` and returns the K clients' mean losses. The K
images move onto the channel axis, client-major (channel k*C + c is
client k's channel c), and both convolutions run as one grouped
convolution (`feature_group_count=K`) whose kernel stacks the clients'
kernels client-major on the output-feature axis, the layout JAX's own
conv batching rule builds. ReLU and max-pool then run on K*32 and K*64
lanes; K is split out once, after the second pool, and the FC layers are
batched matmuls over K. Client k's logits are `cnn_apply`'s on client
k's params and images, and so is the gradient of the summed loss with
respect to client k's params, but where two positive activations tie
for the max of a first-stage pool window (`_maxpool2_lanes`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mlr_init(key, num_classes: int = 10, side: int = 28) -> dict:
    d = side * side
    return {
        "w": jax.random.normal(key, (d, num_classes), jnp.float32) * 0.01,
        "b": jnp.zeros((num_classes,), jnp.float32),
    }


def mlr_apply(params: dict, x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[0], -1) @ params["w"] + params["b"]


def cnn_init(key, num_classes: int = 10) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def conv(k, kh, kw, cin, cout):
        scale = 1.0 / jnp.sqrt(kh * kw * cin)
        return jax.random.normal(k, (kh, kw, cin, cout), jnp.float32) * scale

    def fc(k, a, b):
        return jax.random.normal(k, (a, b), jnp.float32) / jnp.sqrt(a)

    return {
        "c1": conv(k1, 5, 5, 1, 32), "b1": jnp.zeros((32,)),
        "c2": conv(k2, 5, 5, 32, 64), "b2": jnp.zeros((64,)),
        "f1": fc(k3, 3136, 512), "fb1": jnp.zeros((512,)),
        "f2": fc(k4, 512, num_classes), "fb2": jnp.zeros((num_classes,)),
    }


def _maxpool2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


def _maxpool2_lanes(x):
    """`_maxpool2` as a reshape and a max over the window axes.

    On `cnn_apply_clients`' 28x28 stage XLA keeps this pool and its
    backward on the K*32 lanes, where `reduce_window`'s operand takes the
    second convolution's (.., K, 32) layout (K=10, B=32, 18 steps on a
    v5e: 32.5 ms against 36.3 ms). Its gradient splits evenly between
    the elements that tie for a window's max, where `_maxpool2`'s goes
    to one of them; ties at 0 are ReLU's, whose gradient there is 0."""
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def cnn_apply(params: dict, x: jax.Array) -> jax.Array:
    """x (B, 28, 28, 1) -> logits (B, 10)."""
    h = jax.lax.conv_general_dilated(
        x, params["c1"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    ) + params["b1"]
    h = _maxpool2(jax.nn.relu(h))
    h = jax.lax.conv_general_dilated(
        h, params["c2"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    ) + params["b2"]
    h = _maxpool2(jax.nn.relu(h))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["f1"] + params["fb1"])
    return h @ params["f2"] + params["fb2"]


def _conv_clients(h, w, b):
    """K clients' SAME convolutions as one grouped convolution.

    h (B, H, W, K*cin) client-major; w (K, kh, kw, cin, cout) and b
    (K, cout) stacked -> (B, H, W, K*cout) client-major."""
    k, kh, kw, cin, cout = w.shape
    w = jnp.transpose(w, (1, 2, 3, 0, 4)).reshape(kh, kw, cin, k * cout)
    return jax.lax.conv_general_dilated(
        h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=k,
    ) + b.reshape(k * cout)


def cnn_apply_clients(params: dict, x: jax.Array) -> jax.Array:
    """K stacked CNNs on their own images: params leaves (K, ...), x
    (B, K, 28, 28, 1) -> logits (B, K, 10); [:, k] is
    `cnn_apply(params[k], x[:, k])`."""
    b, k = x.shape[:2]
    h = jnp.moveaxis(x, 1, 3).reshape(b, *x.shape[2:4], -1)  # (B, 28, 28, K)
    h = jax.nn.relu(_conv_clients(h, params["c1"], params["b1"]))
    h = _maxpool2_lanes(h)
    h = _maxpool2(jax.nn.relu(_conv_clients(h, params["c2"], params["b2"])))
    h = h.reshape(*h.shape[:3], k, -1)  # (B, 7, 7, K, 64)
    h = jnp.moveaxis(h, 3, 0).reshape(k, b, -1)  # (K, B, 3136)
    h = jax.nn.relu(jnp.einsum("kbi,kio->kbo", h, params["f1"])
                    + params["fb1"][:, None])
    h = jnp.einsum("kbi,kio->kbo", h, params["f2"]) + params["fb2"][:, None]
    return jnp.swapaxes(h, 0, 1)


MODELS = {
    "mlr": (mlr_init, mlr_apply),
    "cnn": (cnn_init, cnn_apply),
}

# client-batched applies of the models that have one (see the docstring)
CLIENTS_APPLY = {"cnn": cnn_apply_clients}


def classification_loss(apply_fn, params, x, y):
    """Mean cross-entropy over the minibatch, axis 0 of `x` and `y`.

    A client-batched apply's logits (B, K, C) against labels (B, K) give
    the K clients' mean losses, (K,)."""
    logits = apply_fn(params, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - ll, axis=0)


def accuracy(apply_fn, params, x, y, batch: int = 2048) -> float:
    correct = 0
    for i in range(0, x.shape[0], batch):
        logits = apply_fn(params, x[i : i + batch])
        correct += int(jnp.sum(jnp.argmax(logits, -1) == y[i : i + batch]))
    return correct / x.shape[0]
