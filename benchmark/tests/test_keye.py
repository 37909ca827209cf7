"""Family ``keye`` and its cell, at the family's tiny widths on the CPU, run by hand
with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- the family meets the contract and its operation count is the least-work sum it says, over
  the keys selected and not the causal half;
- the file states the published config, the cut and every assumed reading;
- the cell rehearses traced and untraced with no problem, and ``correct`` is true;
- the control: the reference in fp8 in the program's place fails at least one compared
  number, while the reference in the stated precision (bf16) passes all; a step that
  returns its state unchanged gives ``correct: false``;
- the limits stand between the chip's sound readings and the control's, both sides on the
  same choices; the reference takes the choices it is given and refuses wrong ones;
- on a program whose pattern-of-layers model has no indexed kind (the parent of the PR
  that added it) the family ends in ``NoResult``, and the readers return nothing where
  there is no trace.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_RESILIENCY_LOG_LEVEL", "WARNING")

from benchmark import flops, harness, rehearse  # noqa: E402

CELL = "keye_vl2_a3b_steady_noprof"
SEEDS = (11, 2147483659, 4000000007)
READERS = ("model.attn_ms", "model.moe_ms", "attn.roofline", "attn.indexer_ms", "attn.select_ms")


#: (worst loss gap of steps 0-2, first gradient's worst leaf, parameter change's worst leaf)
#: as the harness's own comparison read them on the chip at the configuration's size, both
#: sides on the same choices (PR 35, second round; PERF.md section 2): a seed's three from
#: ``benchmark/control.py`` in fp8 and in bf16 on 6 seeds, and from the program's 16 sound
#: seeds of the first three steps and its full runs of the final tree
LIMIT_READINGS = {
    "fp8": [(0.00124, 0.03164, 0.00183), (0.00389, 0.0321, 0.00221), (0.00406, 0.05013, 0.00239),
            (0.00279, 0.04224, 0.00074), (0.00247, 0.08545, 0.00136), (0.00236, 0.06002, 0.00211)],
    "bf16": [(0.00083, 0.00412, 0.00033), (0.00028, 0.00158, 0.0003), (0.00078, 0.00449, 0.00059),
             (0.00063, 0.00482, 0.00146), (0.0006, 0.00704, 0.00044), (0.0003, 0.00426, 0.00132)],
    "sound": [
        (0.00083, 0.00674, 0.0011), (0.0008, 0.01143, 0.00046), (0.00084, 0.00473, 0.00145),
        (0.00043, 0.01404, 0.00035), (0.0016, 0.01073, 0.00136), (0.001, 0.00653, 0.00082),
        (0.0009, 0.00495, 0.00852), (0.0005, 0.00353, 0.0006), (0.00041, 0.0067, 0.00516),
        (0.00049, 0.00209, 0.00143), (0.00051, 0.00504, 0.00043), (0.00059, 0.00439, 0.00169),
        (0.00049, 0.00629, 0.00199), (0.0018, 0.00456, 0.0007), (0.00059, 0.01016, 0.003),
        (0.00036, 0.01015, 0.00054),
        # full runs of the final tree at the committed limits (seeds 2350080001, 2350080103
        # traced, 2350080207, 2350080309)
        (0.00092, 0.00676, 0.00158), (0.00085, 0.01113, 0.00224), (0.00054, 0.01061, 0.00299),
        (0.00081, 0.00578, 0.00146),
    ],
}


@pytest.mark.parametrize("side", list(LIMIT_READINGS))
def test_the_limits_stand_between_the_sound_readings_and_the_control(config, side):
    """Sound runs and the reference in the stated precision are under all three limits,
    the gradient's with 1.5 times of room and more over the sound runs' largest, the loss's
    and the parameter change's with three times. The reference in fp8 is over the gradient
    limit on every seed read (ISSUE 35 asks for most): both sides being compared on the
    same choices, what is left is rounding, and fp8's moves an indexer's gradient by
    percents where bf16's moves it by tenths of one."""
    limits = config["limits"]
    limit = (limits["loss_abs"], limits["grad_norm_gap"], limits["change_norm_gap"])
    readings = LIMIT_READINGS[side]
    over = [any(gap > bound for gap, bound in zip(reading, limit)) for reading in readings]
    if side == "fp8":
        assert all(reading[1] > limit[1] for reading in readings) and sum(over) == len(readings)
        assert "6 of 6" in config["limits_why"]["readings"]
        return
    assert not any(over)
    if side == "sound":
        largest = [max(r[i] for r in readings) for i in range(3)]
        assert largest[1] == 0.01404
        assert 3 * largest[0] < limit[0] and 1.5 * largest[1] < limit[1] and 3 * largest[2] < limit[2]
        assert limit[1] < min(r[1] for r in LIMIT_READINGS["fp8"])


@pytest.fixture
def config():
    """Read anew for every test: ``program_config`` leaves the program's ``choices`` in
    the dict it is given, for the reference that gets the same dict."""
    return harness.load_cell(CELL).config


def test_the_family_meets_the_contract_and_counts_the_least_work(config):
    family = harness.load_family(config)
    assert family.REFERENCE == "keye"
    seq, d = config["batch"][1], config["hidden_size"]
    assert family.routed_share(config) == 1.0  # 8 of 128, 16 held
    # a query reads min(t + 1, 2048) keys: 1,792 on average at 8,192, not the causal 4,096
    assert family.keys_selected(config, seq) == pytest.approx(
        sum(min(t + 1, 2048) for t in range(seq)) / seq) == pytest.approx(1792.125)
    assert family.keys_selected(config, 2048) == pytest.approx(2049 / 2)  # nothing selected
    # the count, written out: four projections, the indexer, the router, an expert, the head
    attention = 2 * d * 32 * 128 + 2 * d * 4 * 128
    assert flops.gqa_projection_params(d, 32, 4, 128) == attention == 18_874_368
    assert family.indexer_params(config) == d * (16 * 64 + 64 + 16) == 2_260_992
    index_scores = 6 * (seq + 1) / 2 * 16 * 64
    products = 12 * 1792.125 * 32 * 128
    layer = (6 * (attention + d * 128 + 3 * d * 768) + 4 * 2_260_992
             + index_scores + products)
    assert family.train_flops_per_token(config, seq) == pytest.approx(
        6 * layer + 6 * d * 18992)
    assert family.train_flops_per_token(config, seq) == pytest.approx(1.826e9, rel=1e-3)
    ops, moved = family.attention_core_cost(config, 1, seq)
    # forward and backward over the selected keys: the products the whole step's count
    # holds, and nothing for the heads' mean probabilities, which the program sums from
    # the probabilities it has
    assert ops == pytest.approx(6 * seq * products)
    assert moved == 6 * seq * 128 * 2 * (5 * 32 + 6 * 4)
    assert not hasattr(family, "indexer_cost")  # no kernel for the index scores, no reader


def test_the_file_states_the_published_config_and_the_cut(config):
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next((r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"), None)
    if published is None:
        pytest.skip("the catalog is not here")
    assert config["source"] == published["source_url"]
    differ = {k for k, v in published["config"].items() if k not in config or config[k] != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"}
    assert config["reduced_from"] == {k: published["config"][k] for k in config["reduced"]}
    assert config["sa_config"] == published["config"]["sa_config"]  # a nested group, whole
    assert config["num_experts"] == config["num_local_experts"]
    assert config["deployment"]["experts_held"] == [0, config["num_experts"]]
    assert config["deployment"]["num_experts"] == published["config"]["num_experts"]
    assert config["vocab_size"] * config["deployment"]["chips_per_layer"] \
        == config["deployment"]["vocab_size"] == published["config"]["vocab_size"]
    for key in ("assumed", "departures", "fit", "limits", "limits_why"):
        assert config[key], key
    for key in ("qk_norm", "text_only", "indexer_query", "indexer_key_norm", "indexer_rotary",
                "indexer_scale", "chunks", "selection", "indexer_loss", "precision",
                "optimizer", "init", "data"):
        assert config["assumed"][key], key
    assert config["batch"] == [1, 8192]


def test_the_program_holds_the_parameters_the_file_counts(config):
    import jax
    import numpy as np

    from tpu_resiliency.models import pattern

    cfg = harness.load_family(config).program_config(config, config["batch"][1])
    described = pattern.describe_params(cfg)
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(  # noqa: E731
        tree, is_leaf=lambda x: isinstance(x, pattern.Leaf)))
    assert count(described) == 659_189_632  # x 12 B = 7.91e9 B of weights and moments
    per_layer = lambda tree: count(tree) // 6  # noqa: E731
    assert per_layer(described["attn"]) == 18_874_368 + 2 * 128 + 2048 + 2_260_992 + 64
    assert per_layer(described["mlp"]) == 2048 + 2048 * 128 + 16 * 3 * 2048 * 768
    assert count({k: described[k] for k in ("embed", "lm_head")}) == 2 * 18992 * 2048
    reference = harness.load_reference(config).describe(config)
    shapes = jax.tree.map(lambda leaf: leaf.shape, described,
                          is_leaf=lambda x: isinstance(x, pattern.Leaf))
    assert shapes == jax.tree.map(lambda leaf: leaf[0], reference,
                                  is_leaf=lambda x: isinstance(x, tuple))


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
    # the indexer's leaves have a gradient (their own loss's) and move, on every side
    for leaf in ("wq_index", "wk_index", "ww_index", "k_index_norm"):
        path = f"['attn']['indexed']['{leaf}']"
        assert reference["grad_norms"][path] > 0 and reference["change_norms"][path] > 0
        assert stated["grad_norms"][path] > 0


@pytest.mark.parametrize("given", ["own", "the_program's", "other_keys", "other_experts"])
def test_the_reference_takes_the_choices_it_is_given_and_refuses_wrong_ones(config, given):
    """``correct`` compares the two sides on the program's keys and experts. Given its own
    choices the reference returns its own loss and gradient to the bit; given the
    program's (another arithmetic: a few flips) a loss close by; given keys or experts
    that step 3 or 5 would not have chosen (the first keys of the causal prefix whatever
    their scores; every expert shifted by one), a loss that is not a number, so that a program that
    chooses wrongly is not followed into ``correct: true``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = {**config, **harness.load_family(config).TINY}
    model = harness.load_reference(cfg)
    params = model.init_params(7, cfg)
    tokens = jnp.asarray(np.random.default_rng(7).integers(0, cfg["vocab_size"], cfg["batch"]), jnp.int32)
    grad = lambda c: jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, c, "f32")))(params)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        own = jax.jit(lambda p: model.forward(p, tokens, cfg, "f32")[2])(params)
        want, want_grad = grad(cfg)
        if given == "own":
            chose = own
        elif given == "the_program's":
            harness.load_family(cfg).program_config(cfg, cfg["batch"][1])
            chose = cfg.pop("choices")(params, tokens)
            differ = float(jnp.mean(chose["selected"] != own["selected"]))
            assert 0 < differ < 0.05  # bfloat16 against float32: a few flips, no other rule
        elif given == "other_keys":
            topk, seq = cfg["sa_config"]["topk"], cfg["batch"][1]
            first = jnp.arange(seq)[None, :] <= jnp.minimum(jnp.arange(seq)[:, None], topk - 1)
            chose = {**own, "selected": jnp.broadcast_to(first, own["selected"].shape)}
        else:
            chose = {**own, "experts": (own["experts"] + 1) % cfg["deployment"]["num_experts"]}
        got, got_grad = grad({**cfg, "choices": lambda p, t: chose})
    if given == "own":
        assert float(got) == float(want)
        for a, b in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    elif given == "the_program's":
        assert 0 < abs(float(got) - float(want)) < 0.05
    else:
        assert np.isnan(float(got))


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


def test_a_program_without_the_indexed_kind_gives_no_result(config, monkeypatch, capsys):
    from tpu_resiliency.models import pattern

    monkeypatch.delattr(pattern, "Indexer")
    with pytest.raises(harness.NoResult):
        harness.load_family(config).program_config(config, 64)
    assert "no indexed attention" in capsys.readouterr().err


def test_a_switch_the_program_does_not_compute_gives_no_result(config, capsys):
    family = harness.load_family(config)
    for key, other in (("norm_topk_prob", False), ("decoder_sparse_step", 2),
                       ("mlp_only_layers", [0]), ("use_sliding_window", True),
                       ("tie_word_embeddings", True), ("attention_bias", True),
                       ("num_local_experts", 128)):
        with pytest.raises(harness.NoResult):
            family.program_config({**config, key: other}, 64)
        assert key in capsys.readouterr().err
    for key, other in (("indexer_num_kv_heads", 2), ("kv_chunk_size", 256)):
        with pytest.raises(harness.NoResult):
            family.program_config({**config, "sa_config": {**config["sa_config"], key: other}}, 64)


def test_the_readers_find_the_scopes_and_return_nothing_without_a_trace(config):
    scope_times = harness.load_by_path("layer_metrics", "scope_times")
    own = harness.load_by_path("layer_metrics", "attn.indexer_ms").SCOPES
    # names as a compile for the v5e writes them (PR 35)
    forward = "jit(train_step)/jvp(attn/full)/indexer/while/body/closed_call/checkpoint/dot_general"
    backward = ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
                "attn/full/indexer/while/body/mul")
    counting = "jit(train_step)/jvp(attn/full)/select/while/body/closed_call/while/body/ge"
    products = "jit(train_step)/jvp(attn/full)/core/while/body/closed_call/dot_general"
    for name in (forward, backward):
        assert own["indexer"].search(name) and not own["select"].search(name)
        assert scope_times.scopes_of(name, "fusion.1") == ["attn"]
    assert own["select"].search(counting) and not own["indexer"].search(counting)
    assert scope_times.scopes_of(counting, "fusion.2") == ["attn"]
    assert scope_times.scopes_of(products, "fusion.3") == ["attn", "attn_core"]
    assert not any(mark.search(products) for mark in own.values())
    assert not own["indexer"].search("params['attn']['indexed']['wq_index']")
    cell = harness.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} >= set(READERS)
    assert not {"moe.experts_roofline", "attn.latent_ms"} & {m["name"] for m in cell.per_layer}
    run = harness.Run(cell, 1, 1.0, True, 0.0, rehearsal=True)
    run.device = {"kind": "TPU v5 lite"}
    try:
        for name in READERS:
            assert harness.load_by_path("layer_metrics", name).read(run) is None
        run.notes.pop("indexed_scopes")
        ops, moved = harness.load_family(config).attention_core_cost(config, *config["batch"])
        run.notes["scope_times"] = {"attn": 0.4, "attn_core": 4 * ops / 197e12, "moe": 0.05,
                                    "moe_experts": 0.0}
        run.notes["indexed_scopes"] = {"indexer": 0.12, "select": 0.03}
        read = lambda name: harness.load_by_path("layer_metrics", name).read(run)  # noqa: E731
        assert moved / 819e9 < ops / 197e12  # the attention products are compute-bound
        assert read("attn.roofline") == pytest.approx(25.0)
        assert read("model.attn_ms") == pytest.approx(400.0)
        assert read("attn.indexer_ms") == pytest.approx(120.0)
        assert read("attn.select_ms") == pytest.approx(30.0)
    finally:
        run.cleanup()
