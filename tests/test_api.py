"""Public API surface tests: the `repro` facade, the unified run()
entrypoint, and FLConfig.validate().

The facade (`src/repro/__init__.py`) is the supported import surface for
scripts/examples/benchmarks — `__all__` is pinned HERE so growing it is a
deliberate, reviewed act. `FedServer.run` is the single run entrypoint
(mode="stepwise" | "scanned"); `run_scanned` survives only as a
warn-once deprecation shim. `FLConfig.validate()` concentrates every
cross-field invariant and is called by both `make_round_fn` and
`init_round_state`, so a bad config fails loudly before anything is
allocated or traced.
"""
import inspect
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

import repro
from repro.core import fl
from repro.data import synthetic

# ------------------------------------------------------------- facade


def test_facade_all_is_pinned():
    assert repro.__all__ == [
        "CSVSink",
        "ClientModel",
        "FLConfig",
        "FedServer",
        "History",
        "JSONLSink",
        "MemorySink",
        "RoundState",
        "SpanTimer",
        "fixed_arrival_schedule",
        "init_round_state",
        "lm_client",
        "make_mesh",
        "make_round_fn",
        "run_manifest",
        "state_from_tree",
        "state_to_tree",
        "telemetry",
        "use_compile_cache",
    ]
    for name in repro.__all__:
        assert getattr(repro, name) is not None


def test_facade_reexports_are_the_real_objects():
    assert repro.FLConfig is fl.FLConfig
    assert repro.RoundState is fl.RoundState
    assert repro.make_round_fn is fl.make_round_fn


def test_make_mesh_axes_are_auto():
    """Explicit axes make with_sharding_constraint(P("data")) refuse the
    flat engines' row sharding; every repo mesh is built Auto."""
    mesh = repro.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_use_compile_cache_keeps_env_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert repro.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_use_compile_cache_defaults_to_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = repro.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert repro.use_compile_cache() == path  # fixed: same each call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    checkout = Path(repro.__file__).resolve().parents[2]
    assert path == str(checkout / ".jax_cache")


def test_use_compile_cache_keys_on_scopes(monkeypatch, tmp_path):
    """The `fl.*` scopes live in the programs' metadata, which the cache
    key leaves out unless asked: a step whose scopes moved must compile
    anew."""
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        repro.use_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          before)


# ------------------------------------------------------ run entrypoint


def test_run_signature_is_pinned():
    sig = inspect.signature(repro.FedServer.run)
    params = list(sig.parameters)
    assert params == ["self", "rounds", "target_acc", "eval_every",
                      "mode", "verbose", "block", "ckpt_dir",
                      "ckpt_every_blocks", "ckpt_keep", "sink",
                      "telemetry_every"]
    p = sig.parameters
    assert p["mode"].kind is inspect.Parameter.KEYWORD_ONLY
    assert p["mode"].default == "stepwise"
    assert p["target_acc"].default is None
    assert p["eval_every"].default == 1
    assert p["block"].default == 8


def _tiny_server(seed=0):
    train, test = synthetic.make_image_task(seed=0, num_train=1500,
                                            num_test=200)
    nodes = synthetic.make_federated(
        train, [("iid", None)] * 2, samples_per_node=150, seed=1)
    cfg = repro.FLConfig(num_clients=2, clients_per_round=2, local_steps=3,
                         base_lr=0.05)
    return repro.FedServer("mlr", cfg, nodes, test, batch_size=50,
                           seed=seed)


def test_run_rejects_unknown_mode():
    s = _tiny_server()
    with pytest.raises(ValueError, match="unknown mode 'turbo'"):
        s.run(1, mode="turbo")


def test_run_scanned_shim_warns_once_and_delegates():
    s_shim, s_run = _tiny_server(), _tiny_server()
    repro.FedServer._warned_run_scanned = False
    with pytest.warns(DeprecationWarning, match="run_scanned"):
        h_shim = s_shim.run_scanned(4, eval_every=2, block=2)
    # warn-once: a second call must stay silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        s_shim.run_scanned(2, eval_every=2, block=2)
    h_run = s_run.run(4, eval_every=2, mode="scanned", block=2)
    np.testing.assert_allclose(h_shim.loss, h_run.loss, rtol=1e-6)
    np.testing.assert_allclose(h_shim.accuracy, h_run.accuracy, atol=1e-6)


# -------------------------------------------------- FLConfig.validate


def _cfg(**kw):
    base = dict(num_clients=10, clients_per_round=10, local_steps=4)
    base.update(kw)
    return repro.FLConfig(**base)


def test_validate_returns_self_for_chaining():
    cfg = _cfg()
    assert cfg.validate() is cfg


BAD_CONFIGS = [
    (dict(mode="lockstep"), "unknown mode"),
    (dict(method="fedsgd"), "unknown method"),
    (dict(engine="gpu"), "unknown engine"),
    (dict(transport="int2"), "unknown transport"),
    (dict(downlink="int4"), "unknown downlink"),
    (dict(error_feedback=True), "transport='f32' has none"),
    (dict(aggregation="async"), "unknown aggregation"),
    (dict(aggregation="buffered", mode="sequential"),
     "requires mode='parallel'"),
    (dict(aggregation="buffered", stale_angles=True), "stale_angles"),
    (dict(aggregation="buffered", buffer_m=11), "buffer_m=11 must be in"),
    (dict(aggregation="buffered", staleness_beta=-0.5),
     "staleness_beta=-0.5 must be >= 0"),
    (dict(aggregation="buffered", straggle_prob=1.5),
     "straggle_prob=1.5 must be a"),
    (dict(aggregation="buffered", dropout_prob=-0.1),
     "dropout_prob=-0.1 must be a"),
    (dict(aggregation="buffered", straggle_prob=0.2, straggle_max=0),
     "straggle_max=0 must be >= 1"),
    (dict(buffer_m=5), "requires aggregation='buffered'"),
    (dict(straggle_prob=0.2), "requires aggregation='buffered'"),
    (dict(dropout_prob=0.1), "requires aggregation='buffered'"),
]


@pytest.mark.parametrize("kw,match", BAD_CONFIGS,
                         ids=[m for _, m in BAD_CONFIGS])
def test_validate_rejects(kw, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**kw).validate()


def test_invalid_config_fails_before_allocation_and_tracing():
    """Both entry points run validate(): neither a round function nor a
    RoundState can be built from an invalid config."""
    bad = _cfg(buffer_m=5)  # buffered knob without aggregation="buffered"
    params = {"w": jnp.zeros((3, 2)), "b": jnp.zeros((2,))}
    with pytest.raises(ValueError, match="requires aggregation='buffered'"):
        repro.init_round_state(bad, params)
    with pytest.raises(ValueError, match="requires aggregation='buffered'"):
        repro.make_round_fn(lambda p, b: 0.0, bad)
