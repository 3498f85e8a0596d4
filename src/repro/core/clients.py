"""Client models: what `FedServer` needs to know of the model its clients
train.

A `ClientModel` gives the model's init, its per-client loss, the eval
over a held-out set and the stacking of the clients' data onto the
device. `FedServer` takes one, or the name of one of the paper's image
models (`small.MODELS`), for which `image_client` builds it.

* `image_client(name)`: softmax cross-entropy on (images, labels); the
  CNN's loss carries its client-batched form as `loss.clients`; eval is
  test accuracy (`driver.make_eval_fn`).
* `lm_client(cfg)`: a `transformer` language model trained on token
  sequences, each client's data a `Dataset` of input ids `x` (n, T) and
  their next-token targets `y` (n, T). Its loss returns the MoE layers'
  expert-row count beside it (`loss.has_aux`), which the sequential
  round adds up into the round's metrics as "expert_rows". Its eval
  returns next-token accuracy, the mean loss ("eval_loss") and the eval's
  expert rows ("eval_expert_rows"), computed a few sequences at a time so
  that the (n, T, V) logits never exist.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import driver
from repro.models import small, transformer


@dataclasses.dataclass(frozen=True)
class ClientModel:
    init: Callable  # key -> params
    loss: Callable  # (params, batch) -> loss, or (loss, counters) with has_aux
    make_eval: Callable  # test Dataset -> eval_fn(params)
    apply: Optional[Callable] = None  # (params, x) -> logits, host eval
    # (nodes, batch_size, local_steps) -> driver.ClientData
    stack: Callable = driver.stack_nodes


def image_client(name: str) -> ClientModel:
    """The paper's image model `name` ("mlr" or "cnn")."""
    init_fn, apply_fn = small.MODELS[name]

    def loss_of(apply):
        def loss(params, batch):
            x, y = batch
            return small.classification_loss(apply, params, x, y)

        return loss

    loss_fn = loss_of(apply_fn)
    if name in small.CLIENTS_APPLY:  # batch leaves (B, K, ...)
        loss_fn.clients = loss_of(small.CLIENTS_APPLY[name])
    return ClientModel(
        init=init_fn, loss=loss_fn, apply=apply_fn,
        make_eval=lambda test: driver.make_eval_fn(apply_fn, test.x, test.y))


def lm_client(cfg, eval_chunk: int = 1) -> ClientModel:
    """A `transformer` language model of config `cfg`; the eval runs
    `eval_chunk` held-out sequences at a time."""

    def loss(params, batch):
        x, y = batch
        value, rows = transformer.next_token_loss(params, cfg, x, y)
        return value, {"expert_rows": rows}

    loss.has_aux = True

    def make_eval(test):
        n, t = test.x.shape
        if n % eval_chunk:
            raise ValueError(f"{n} eval sequences do not split into chunks "
                             f"of {eval_chunk}")
        xs = jnp.asarray(test.x).reshape(n // eval_chunk, eval_chunk, t)
        ys = jnp.asarray(test.y).reshape(n // eval_chunk, eval_chunk, t)

        def eval_fn(params):
            def body(tot, xy):
                out = transformer.next_token_eval(params, cfg, *xy)
                return jax.tree.map(jnp.add, tot, out), None

            zero = (jnp.int32(0), jnp.float32(0.0), jnp.int32(0))
            (correct, nll, rows), _ = jax.lax.scan(body, zero, (xs, ys))
            return {"accuracy": correct.astype(jnp.float32) / (n * t),
                    "eval_loss": nll / (n * t), "eval_expert_rows": rows}

        return eval_fn

    return ClientModel(
        init=jax.jit(functools.partial(transformer.init_params, cfg=cfg)),
        loss=loss, make_eval=make_eval)
