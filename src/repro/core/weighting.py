"""FedAdp adaptive weighting (paper Eqs. 8-11) and the FedAvg baseline.

All functions are pure and jit-safe; shapes are (K,) vectors over the
participating clients of one round.

Numerical notes:
  * angles are computed in f32 with the cosine clipped to [-1+eps, 1-eps]
    before arccos (gradient of arccos blows up at the boundary, and bf16
    dots can stray slightly outside [-1, 1]).
  * Eq. 11's two cases collapse to a single log-softmax:
      psi_i = D_i e^{f_i} / sum_j D_j e^{f_j} = softmax(f + log D)_i
    (line 1 of Eq. 11 is the equal-D special case).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

DEFAULT_ALPHA = 5.0
_EPS = 1e-7


class AngleState(NamedTuple):
    """Server-side smoothed-angle state (paper Eq. 9), one slot per client.

    `count` is the number of rounds each client has participated in so far
    (the paper's `t` in Eq. 9 — with full participation it is the round
    index; with subset selection it is the per-client participation count).
    """

    smoothed: jax.Array  # (N,) f32, radians
    count: jax.Array  # (N,) i32

    @classmethod
    def init(cls, num_clients: int) -> "AngleState":
        return cls(
            smoothed=jnp.zeros((num_clients,), jnp.float32),
            count=jnp.zeros((num_clients,), jnp.int32),
        )


def cosine_from_stats(dot: jax.Array, sq_a: jax.Array, sq_b: jax.Array) -> jax.Array:
    """cos(theta) from <a,b>, ||a||^2, ||b||^2 — guards zero norms."""
    denom = jnp.sqrt(jnp.maximum(sq_a, _EPS)) * jnp.sqrt(jnp.maximum(sq_b, _EPS))
    return jnp.clip(dot / denom, -1.0 + _EPS, 1.0 - _EPS)


def instantaneous_angle(dot: jax.Array, sq_local: jax.Array, sq_global: jax.Array) -> jax.Array:
    """theta_i(t), Eq. 8 — in radians, elementwise over (K,) stats."""
    return jnp.arccos(cosine_from_stats(dot, sq_local, sq_global))


def update_smoothed_angle(
    state: AngleState, theta: jax.Array, selected: jax.Array
) -> AngleState:
    """Eq. 9 applied to the selected clients' slots.

    selected: (N,) bool mask; theta: (N,) with valid entries where selected.
    """
    new_count = state.count + selected.astype(jnp.int32)
    t = jnp.maximum(new_count, 1).astype(jnp.float32)
    smoothed_upd = ((t - 1.0) * state.smoothed + theta) / t
    smoothed = jnp.where(selected, smoothed_upd, state.smoothed)
    return AngleState(smoothed=smoothed, count=new_count)


def smoothed_angle(theta: jax.Array, smoothed_prev: jax.Array,
                   count_prev: jax.Array) -> jax.Array:
    """Eq. 9 on the selected clients' own slots: their smoothed angle
    after this round, from the slots' values before it — the same float
    ops `update_smoothed_angle` writes back. All (K,)."""
    cnt = count_prev.astype(jnp.float32) + 1.0
    return ((cnt - 1.0) * smoothed_prev + theta) / cnt


def gompertz(theta: jax.Array, alpha: float = DEFAULT_ALPHA) -> jax.Array:
    """Non-linear contribution mapping f(theta), Eq. 10.

    Decreasing in theta; ~alpha for small angles, ~alpha(1-1/e)·small for
    theta -> pi/2 and beyond.
    """
    return alpha * (1.0 - jnp.exp(-jnp.exp(-alpha * (theta - 1.0))))


def fedadp_weights(
    smoothed_theta: jax.Array,
    data_sizes: jax.Array,
    alpha: float = DEFAULT_ALPHA,
) -> jax.Array:
    """Eq. 11 for the K participating clients: softmax(f(theta~) + log D)."""
    f = gompertz(smoothed_theta.astype(jnp.float32), alpha)
    logits = f + jnp.log(data_sizes.astype(jnp.float32))
    return jax.nn.softmax(logits)


def fedavg_weights(data_sizes: jax.Array) -> jax.Array:
    """psi_i = D_i / sum D (Eq. 1)."""
    d = data_sizes.astype(jnp.float32)
    return d / jnp.sum(d)


# ---------------------------------------------------------------- buffered
# Staleness-aware variants for the buffered-async server (FedBuff-style):
# the flush aggregates only the LANDED reports of the in-flight cohort,
# and a report that waited `age` model versions between pulling the
# global params and being applied is discounted by exp(-beta * age) on
# top of its Gompertz contribution weight — late low-contribution nodes
# are doubly suppressed. With every report landed at age 0 the math below
# reduces BIT-EXACTLY to the synchronous Eqs. 1/11 (subtracting
# beta * 0 == 0.0 and multiplying by exp(-0.0) == 1.0 are exact), which
# is what pins buffered(buffer_m=K, no stragglers) == sync.


def staleness_discount(age: jax.Array, beta: float) -> jax.Array:
    """exp(-beta * age): the multiplicative staleness decay of a report
    that waited `age` server model versions before being aggregated."""
    return jnp.exp(-beta * age.astype(jnp.float32))


def buffered_fedadp_weights(
    smoothed_theta: jax.Array,
    data_sizes: jax.Array,
    age: jax.Array,
    landed: jax.Array,
    alpha: float = DEFAULT_ALPHA,
    beta: float = 0.0,
) -> jax.Array:
    """Eq. 11 over the landed reports with the staleness decay folded into
    the softmax logits: softmax(f(theta~) + log D - beta * age), non-landed
    rows at -inf so they get exactly zero weight. Returns zeros when no
    report has landed (the flush is skipped then anyway)."""
    f = gompertz(smoothed_theta.astype(jnp.float32), alpha)
    logits = (f + jnp.log(data_sizes.astype(jnp.float32))
              - beta * age.astype(jnp.float32))
    logits = jnp.where(landed, logits, -jnp.inf)
    w = jax.nn.softmax(logits)
    return jnp.where(jnp.any(landed), w, jnp.zeros_like(w))


def buffered_fedavg_weights(
    data_sizes: jax.Array,
    age: jax.Array,
    landed: jax.Array,
    beta: float = 0.0,
) -> jax.Array:
    """Eq. 1 over the landed reports with the staleness decay applied
    multiplicatively: psi_i = D_i e^{-beta age_i} / sum_landed (same)."""
    s = jnp.where(landed,
                  data_sizes.astype(jnp.float32) * staleness_discount(age, beta),
                  0.0)
    return s / jnp.maximum(jnp.sum(s), 1e-12)


def expected_contribution(weights: jax.Array, cos_theta: jax.Array) -> jax.Array:
    """E_{i|t}[cos theta_i] — the Theorem-1 expectation term.

    Theorem 2 asserts this is >= under FedAdp weights than under FedAvg
    weights; used by the property tests.
    """
    return jnp.sum(weights * cos_theta)
