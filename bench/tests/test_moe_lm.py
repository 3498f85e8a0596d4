"""The MoE language-model family (`bench/families/moe_lm.py`) off the
chip: its traffic rules, its counts at the cell's size, its metric
readers, and a CPU run of a tiny cell of the family through
`bench.run.main` (added to a copy of the benchmark as a configuration, a
traffic file and a workload entry), correct when sound and not correct
with half of each minibatch left out of the program's loss."""
import json
import os
import shutil
from types import SimpleNamespace

import jax
import pytest

from bench import run
from bench.scopes import Op

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "bench", "peaks.json")) as _f:
    PEAKS = json.load(_f)["devices"]["TPU v5 lite"]


def _json(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


FAMILY = run.load_family("moe_lm", ROOT)
TRAFFIC = dict(_json("bench/traffic/dsv2lite.silo.json"), model="dsv2lite")
CONFIG = _json("bench/configs/dsv2lite.json")


def test_cell_traffic_is_valid():
    FAMILY.validate_traffic(TRAFFIC)


@pytest.mark.parametrize("change,match", [
    (lambda t: t["fl"].update(local_steps=40), "epoch"),
    (lambda t: t["data"].update(relabel=[False, True]), "silos"),
    (lambda t: t["fl"].update(clients_per_round=2), "every silo"),
    (lambda t: t["fl"].update(mode="parallel"), "sequential"),
    (lambda t: t.update(eval_chunk=3), "eval chunk"),
], ids=["tau-past-epoch", "silos", "cohort", "parallel", "eval-chunk"])
def test_bad_traffic_is_refused(change, match):
    t = json.loads(json.dumps(TRAFFIC))
    change(t)
    with pytest.raises(ValueError, match=match):
        FAMILY.validate_traffic(t)


def test_cell_counts():
    """The round's and the eval's model FLOPs at the cell's size, and the
    config's parameter count by its parts."""
    assert round(FAMILY.round_flops(CONFIG, TRAFFIC) / 1e12, 1) == 55.9
    assert round(FAMILY.eval_flops(CONFIG, TRAFFIC) / 1e12, 1) == 9.3
    parts = CONFIG["params_by_part"]
    moe = CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"]
    total = (CONFIG["num_hidden_layers"] * (parts["attention_per_layer"]
                                            + parts["norms_per_layer"])
             + parts["dense_mlp"]
             + moe * (parts["held_experts_per_moe_layer"]
                      + parts["shared_experts_per_moe_layer"]
                      + parts["router_per_moe_layer"])
             + parts["embedding"] + parts["head"] + parts["final_norm"])
    assert total == CONFIG["params"] == 535_060_992


def test_gmm_counts():
    """One trained row of one layer runs the three products forward twice
    and backward once; the weights move once per product and pass."""
    d, f = CONFIG["hidden_size"], CONFIG["moe_intermediate_size"]
    assert FAMILY.gmm_flops(CONFIG, 1, 0) == 24 * d * f
    assert FAMILY.gmm_flops(CONFIG, 0, 1) == 6 * d * f
    assert FAMILY.gmm_bytes(CONFIG, 0, 0, 0, 1) == 4 * 3 * 8 * d * f
    assert FAMILY.gmm_bytes(CONFIG, 1, 0, 0, 0) == 4 * 12 * (d + f)
    # 2 rounds, 1 eval: 4 silos x 2 steps x 2 passes, 8 chunks; 4 layers
    assert FAMILY.gmm_calls(CONFIG, TRAFFIC, 2, 1) == (128, 32)


def _ctx(ops, rounds=2, evals=2, rows=(3000, 3000), eval_rows=(100, 100)):
    hists = [SimpleNamespace(expert_rows=list(rows),
                             eval_expert_rows=list(eval_rows))]
    scopes = SimpleNamespace(ops={"/device:TPU:0": ops},
                             leaf_ops=lambda: iter(ops))
    return SimpleNamespace(scopes=scopes, rounds=rounds, evals=evals,
                           histories=hists, family=FAMILY, config=CONFIG,
                           traffic=TRAFFIC, peaks=PEAKS)


OPS = [Op("fusion.1", 0.0, 0.010,
          "jit(run_block)/while/body/fl.local_train/model.mla/dot", "m"),
       Op("fusion.2", 0.010, 0.012,
          "transpose(jvp(model.moe))/model.moe_gmm/ragged_dot", "m"),
       Op("fusion.3", 0.012, 0.013, "fl.local_train/model.moe/gather", "m"),
       Op("fusion.4", 0.013, 0.020, "fl.local_train/model.head/dot", "m")]


def test_scope_metrics_read_their_scopes():
    ctx = _ctx(OPS)
    mla = run.load_metric("mla_ms", ROOT).read(ctx)
    expert = run.load_metric("expert_ms", ROOT).read(ctx)
    assert mla == pytest.approx(1e3 * 0.010 / 2)
    assert expert == pytest.approx(1e3 * 0.003 / 2)
    share = run.load_metric("expert_roofline", ROOT).read(ctx)
    flops = FAMILY.gmm_flops(CONFIG, 6000, 200)
    nbytes = FAMILY.gmm_bytes(CONFIG, 6000, 200,
                              *FAMILY.gmm_calls(CONFIG, TRAFFIC, 2, 2))
    least = max(flops / PEAKS["bf16_flops"], nbytes / PEAKS["hbm_bytes_per_s"])
    assert share == pytest.approx(100 * least / 0.002)


def test_scope_metrics_silent_without_their_scopes():
    """A program without the model's scopes or its row counter (the
    parent of the change that adds them) reads nothing."""
    ops = [o._replace(path="fl.local_train/dot") for o in OPS]
    for name in ("mla_ms", "expert_ms", "expert_roofline"):
        assert run.load_metric(name, ROOT).read(_ctx(ops)) is None
    ctx = _ctx(OPS, rows=(), eval_rows=())
    assert run.load_metric("expert_roofline", ROOT).read(ctx) is None


# ----------------------------------------------------- a tiny cell, on CPU

TINY = {"hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
        "moe_intermediate_size": 32, "num_attention_heads": 2,
        "num_key_value_heads": 2, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 3,
        "n_routed_experts": 4, "num_experts_per_tok": 3, "vocab_size": 256}


def _tiny_config():
    conf = dict(CONFIG, name="dsv2tiny", model="dsv2tiny", **TINY)
    conf["deployment"] = dict(conf["deployment"], chips_per_layer=2,
                              held_experts=[0, 1, 2, 3], router_outputs=8)
    return conf


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = _tiny_config()
    traffic = json.loads(json.dumps(TRAFFIC))
    traffic["data"].update(sequences=8, seq_len=32, eval_sequences=4)
    traffic["eval_chunk"] = 2
    traffic["trace_seconds"] = 1
    del traffic["model"]
    fam = run.load_family("moe_lm", root)
    s = fam.setup_from(dict(traffic, model="dsv2lite"))._replace(
        d=64, heads=2, kv_rank=32, nope=16, rope=8, v_dim=16, dense_ff=96,
        expert_ff=32, router=8, held=(0, 1, 2, 3), top_k=3, vocab=256,
        moe_layers=2)
    shapes = jax.eval_shape(lambda k: fam.init_params(s, k), jax.random.key(0))
    conf["params"] = sum(a.size for a in jax.tree.leaves(shapes))
    with open(os.path.join(root, "bench", "configs", "dsv2tiny.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "bench", "traffic", "dsv2tiny.silo.json"),
              "w") as f:
        json.dump(traffic, f)
    bench = _json("BENCHMARK.json")
    bench["configs"].append(dict(bench["configs"][-1], name="dsv2tiny",
                                 file="bench/configs/dsv2tiny.json"))
    bench["workloads"].append({"name": "dsv2tiny.silo", "config": "dsv2tiny",
                               "traffic": "silo", "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "dsv2lite.silo" in m.get("workloads", []):
            m["workloads"].append("dsv2tiny.silo")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def drive(root, capsys, trace=0):
    cell = run.load_cell("dsv2tiny.silo", root)
    rc = run.main(["--workload", cell.name, "--seed", "3000000017",
                   "--seconds", "1", "--trace", str(trace)],
                  require_tpu=False, root=root, cell=cell, peaks=PEAKS)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_tiny_cell_is_correct(tiny_root, capsys):
    out = drive(tiny_root, capsys)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"round_s", "setup_s"}
    assert set(out["check"]) == set(TRAFFIC["check"]["limits"])
    # on the CPU both sides compute in float32: far inside every limit
    for row in out["check"].values():
        assert row["value"] < row["limit"] / 10, out["check"]


def test_tiny_cell_traced(tiny_root, capsys):
    out = drive(tiny_root, capsys, trace=1)
    assert out["correct"], out["check"]
    assert out["metrics"]["step_mfu"]["value"] > 0


def test_tiny_cell_half_batch_is_not_correct(tiny_root, monkeypatch, capsys):
    from repro.models import transformer

    real = transformer.next_token_loss

    def half(params, cfg, x, y):
        h = x.shape[0] // 2
        return real(params, cfg, x[:h], y[:h])

    monkeypatch.setattr(transformer, "next_token_loss", half)
    jax.clear_caches()
    out = drive(tiny_root, capsys)
    assert not out["correct"], out["check"]


def test_tiny_cell_eval_matches_the_reference(tiny_root):
    """The program's eval (next-token accuracy and mean loss over the
    held-out set, in chunks) against the family reference's, round by
    round: both sides compute in float32 on the CPU."""
    import repro

    cell = run.load_cell("dsv2tiny.silo", tiny_root)
    fam = run.load_family("moe_lm", tiny_root)
    tr = cell.traffic
    nodes, test = fam.make_data(tr)
    seed = 3_000_000_017
    server = fam.make_server(repro, repro.FLConfig(**tr["fl"]).validate(), tr,
                             nodes, test, seed)
    server.reset(seed)
    hist = server.run(2, eval_every=1, mode="scanned", block=2)
    want = fam.follow(fam.reference_setup(tr),
                      fam.reference_data(nodes, test), seed, 2, 1)
    assert hist.accuracy == pytest.approx(list(want["acc"].values()),
                                          abs=1e-6)
    assert hist.eval_loss == pytest.approx(list(want["eval_loss"].values()),
                                           rel=1e-5)
