"""`mla_attn_ms` reads the fused attention kernels under `model.mla_attn`,
nested in `model.mla` (which `mla_ms` still counts), on made-up leaf ops
with the name paths a described-v5e compile gives them: the forward, its
rematerialization and the dkv and dq kernels of the backward. A program
whose attention runs as einsums reads nothing."""
import os
from types import SimpleNamespace

import pytest

from bench import run
from bench.scopes import Op

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BODY = "jit(run_block)/while/body/fl.local_train/while/body/closed_call/"
SPLASH = "model.mla/model.mla_attn/vmap(jit(_splash_attention))/"
OPS = [
    Op("splash_mha_fwd_residuals.8", 0.0, 0.004,
       "jvp(" + BODY + SPLASH + "splash_mha_fwd_residuals/pallas_call)", "m"),
    Op("splash_mha_fwd_residuals.10", 0.004, 0.008,
       "transpose(jvp(" + BODY + "checkpoint/rematted_computation/" + SPLASH
       + "splash_mha_fwd_residuals/pallas_call))", "m"),
    Op("splash_mha_dkv_no_residuals.4", 0.008, 0.016,
       "transpose(jvp(" + BODY + "checkpoint/" + SPLASH
       + "splash_mha_dkv_no_residuals/pallas_call))", "m"),
    Op("splash_mha_dq_no_residuals.4", 0.016, 0.022,
       "transpose(jvp(" + BODY + "checkpoint/" + SPLASH
       + "splash_mha_dq_no_residuals/pallas_call))", "m"),
    Op("fusion.1", 0.022, 0.025, BODY + "model.mla/dot_general", "m"),
    Op("fusion.2", 0.025, 0.030, BODY + "model.moe/gather", "m"),
]


def _ctx(ops, rounds=2):
    return SimpleNamespace(
        scopes=SimpleNamespace(ops={"/device:TPU:0": ops},
                               leaf_ops=lambda: iter(ops)),
        rounds=rounds)


def test_reads_the_kernels_nested_in_mla():
    ctx = _ctx(OPS)
    attn = run.load_metric("mla_attn_ms", ROOT).read(ctx)
    assert attn == pytest.approx(1e3 * 0.022 / 2)
    mla = run.load_metric("mla_ms", ROOT).read(ctx)
    assert mla == pytest.approx(1e3 * 0.025 / 2)


@pytest.mark.parametrize("path", [
    BODY + "model.mla/dot_general",  # the einsum core
    BODY + "model.mla_attention/dot_general",  # another scope's prefix
    BODY + "model.moe/model.moe_gmm/pallas_call"])
def test_silent_without_its_scope(path):
    ops = [o._replace(path=path) for o in OPS]
    assert run.load_metric("mla_attn_ms", ROOT).read(_ctx(ops)) is None


def test_silent_without_rounds():
    assert run.load_metric("mla_attn_ms", ROOT).read(
        _ctx(OPS, rounds=0)) is None
