"""Family ``laguna`` and its cell, at the family's tiny widths on the CPU, run by hand
with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- the family meets the contract and its operation count is the least-work sum it says;
- the cell rehearses traced and untraced with no problem, and ``correct`` is true;
- the control: the reference in fp8 in the program's place fails at least one compared
  number, while the reference in the stated precision (bf16) passes all;
- a step that returns its state unchanged gives ``correct: false``;
- on a program without ``models/pattern.py`` (the parent of the PR that added it) the
  family ends in ``NoResult``, and the four readers return nothing where there is no trace.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_RESILIENCY_LOG_LEVEL", "WARNING")

from benchmark import flops, harness, rehearse  # noqa: E402

CELL = "laguna_xs2_steady_noprof"
SEEDS = (11, 2147483659, 4000000007)
READERS = ("model.attn_ms", "model.moe_ms", "attn.roofline", "moe.experts_roofline")


@pytest.fixture(scope="module")
def config():
    return harness.load_cell(CELL).config


def test_the_family_meets_the_contract_and_counts_the_least_work(config):
    family = harness.load_family(config)
    assert family.REFERENCE == "laguna"
    layers = family.layers_of(config)
    assert layers == [("full", 48, "dense"), ("sliding", 64, "sparse"), ("sliding", 64, "sparse"),
                      ("sliding", 64, "sparse"), ("full", 48, "sparse")]
    seq = config["batch"][1]
    d, dh, hkv = config["hidden_size"], config["head_dim"], config["num_key_value_heads"]
    assert family.routed_share(config) == 1.0  # 8 of 256, 32 held: one routed expert a token
    # the count, written out: projections with the gate, band or causal half, MLPs, head
    attention = lambda h: d * h * dh * 2 + 2 * d * hkv * dh + d * h  # noqa: E731
    sparse = d * 256 + 2 * 3 * d * 512
    matmul = (2 * attention(48) + 3 * attention(64) + 3 * d * config["intermediate_size"]
              + 4 * sparse + d * config["vocab_size"])
    band = (512 * 513 / 2 + (seq - 512) * 512) / seq
    products = 2 * 12 * (seq / 2) * 48 * dh + 3 * 12 * band * 64 * dh
    assert family.train_flops_per_token(config, seq) == pytest.approx(6 * matmul + products)
    ops, moved = family.attention_core_cost(config, 1, seq)
    assert ops == pytest.approx(seq * products) and moved > 0
    ops, moved = family.expert_products_cost(config, 1, seq)
    assert ops == pytest.approx(4 * seq * 6 * flops.swiglu_params(d, 512))


def test_the_file_states_the_published_config_and_the_cut(config):
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next((r for r in rows if r["name"] == "Laguna-XS.2"), None)
    if published is None:
        pytest.skip("the catalog is not here")
    assert config["source"] == published["source_url"]
    differ = {k for k, v in published["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert config["reduced_from"] == {k: published["config"][k] for k in config["reduced"]}
    assert config["deployment"]["experts_held"] == [0, config["num_experts"]]
    assert config["vocab_size"] * config["deployment"]["chips_per_layer"] \
        == config["deployment"]["vocab_size"]


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_and_is_correct(trace):
    run, metrics = rehearse.rehearse(CELL, SEEDS[2], 1.0, trace)
    result = run.result(metrics)
    assert result["correct"] is True, run.problems
    assert len(result["compared"]) == 5
    if not trace:
        assert set(result["metrics"]) == {"tokens_per_s", "step_ms_p95", "setup_s"}
    else:  # the CPU has no device plane: the readers of device time leave their metric out
        assert not set(READERS) & set(result["metrics"])


@pytest.mark.parametrize("seed", SEEDS)
def test_control_in_fp8_fails_and_stated_precision_passes(config, seed):
    import numpy as np

    from benchmark.reference import train

    cfg = {**config, **harness.load_family(config).TINY}
    cell = harness.Cell("control", 1, "tiny", cfg, "", {}, [], [])
    run = harness.Run(cell, seed, 1.0, False, 0.0, rehearsal=True)
    batches = [np.random.default_rng([seed, i]).integers(
        0, cfg["vocab_size"], cfg["batch"]).astype(np.int32) for i in range(3)]
    reference = train.follow(seed % (1 << 32), cfg, batches, "f32")
    stated = train.follow(seed % (1 << 32), cfg, batches, "bf16")
    control = train.follow(seed % (1 << 32), cfg, batches, "fp8")
    try:
        ok = harness.compare_with_reference(run, stated, reference, cfg["limits"])
        assert all(row["ok"] for row in ok), ok
        run.problems.clear()
        bad = harness.compare_with_reference(run, control, reference, cfg["limits"])
        assert not all(row["ok"] for row in bad), bad
    finally:
        run.cleanup()


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax

    real_build = harness.Session.build_state

    def broken_build(self):
        state = real_build(self)
        sound = jax.jit(self.train_step)  # no donation: the state handed in survives
        self.step = lambda params, opt_state, tokens: (
            params, opt_state, sound(params, opt_state, tokens)[2])
        return state

    monkeypatch.setattr(harness.Session, "build_state", broken_build)
    run, metrics = rehearse.rehearse(CELL, SEEDS[1], 1.0, False)
    result = run.result(metrics)
    assert result["correct"] is False and result["failed"] >= 1
    assert any("change_norms" in p or "grad_norms" in p for p in run.problems), run.problems


def test_a_program_without_the_model_gives_no_result(config, monkeypatch, capsys):
    import tpu_resiliency.models

    monkeypatch.setitem(sys.modules, "tpu_resiliency.models.pattern", None)
    monkeypatch.delattr(tpu_resiliency.models, "pattern", raising=False)
    with pytest.raises(harness.NoResult):
        harness.load_family(config).program_config(config, 64)
    assert "no pattern-of-layers model" in capsys.readouterr().err


def test_the_readers_find_the_scopes_and_return_nothing_without_a_trace(config):
    scope_times = harness.load_by_path("layer_metrics", "scope_times")
    # names as a compile for the v5e writes them (PR 28)
    assert scope_times.scopes_of(
        "jit(train_step)/jvp(attn/full)/core/closed_call/while/body/closed_call/checkpoint/sub",
        "fusion.3613") == ["attn", "attn_core"]
    assert scope_times.scopes_of(
        "jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/attn/sliding/core/"
        "while/body/closed_call/checkpoint/exp", "fusion.9") == ["attn", "attn_core"]
    assert scope_times.scopes_of("jit(train_step)/jvp(attn/sliding)/mul", "fusion.2") == ["attn"]
    assert scope_times.scopes_of(
        "jit(train_step)/transpose(jvp(jvp()))/checkpoint/attn/full/dot_general", "fusion.7"
    ) == ["attn"]
    assert scope_times.scopes_of(
        "jit(train_step)/transpose(jvp(jvp()))/checkpoint/moe/experts/jit(silu)/mul", "fusion.1"
    ) == ["moe", "moe_experts"]
    assert scope_times.scopes_of("jit(train_step)/jvp(moe/dispatch)/gather", "fusion.5") == ["moe"]
    assert scope_times.scopes_of("ragged-dot-none", "ragged-dot-none.2") == ["moe", "moe_experts"]
    assert scope_times.scopes_of("params['attn']['full']['wq']", "copy.4") == []
    assert scope_times.scopes_of("jit(train_step)/jvp(mlp/dense)/dot_general", "fusion.2") == []
    cell = harness.load_cell(CELL)
    run = harness.Run(cell, 1, 1.0, True, 0.0, rehearsal=True)
    run.device = {"kind": "TPU v5 lite"}
    try:
        for name in READERS:
            assert harness.load_by_path("layer_metrics", name).read(run) is None
        # with times to read: the family's floor over the time, and the sums in ms
        ops, moved = harness.load_family(config).attention_core_cost(config, *config["batch"])
        run.notes["scope_times"] = {"attn": 0.4, "attn_core": 2 * ops / 197e12, "moe": 0.1,
                                    "moe_experts": 0.0}
        read = lambda name: harness.load_by_path("layer_metrics", name).read(run)  # noqa: E731
        assert moved / 819e9 < ops / 197e12  # the attention products are compute-bound
        assert read("attn.roofline") == pytest.approx(50.0)
        assert read("model.attn_ms") == pytest.approx(400.0)
        assert read("model.moe_ms") == pytest.approx(100.0)
        assert read("moe.experts_roofline") is None  # no op of that scope in the trace
    finally:
        run.cleanup()
