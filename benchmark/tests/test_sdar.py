"""Family ``sdar`` and its cell, at the family's tiny widths on the CPU, run by hand with
the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- the cut is the one the configuration's entry states: 645,623,296 parameters, and each
  part; the file states the published config, the cut (three keys), the deployment and
  every assumed reading;
- the family meets the contract and its counts are a hand count, at the cell's size and at
  ``TINY``: both halves of the stream through the layers, the head once, the attention
  products over the pairs the mask allows;
- the cell is in the lists ISSUE 48 names and in no other, and ``BENCHMARK.json`` differs
  from its parent's by one configuration, one cell and one per-layer entry;
- the cell rehearses traced and untraced with no problem, and ``correct`` is true;
- the control: the reference in fp8 in the program's place fails at least one compared
  number, while the reference in the stated precision (bf16) passes all; a step that
  returns its state unchanged gives ``correct: false``;
- the limits stand between the chip's sound readings and the control's; the reference
  takes the experts it is given and refuses shifted ones;
- on a program whose pattern-of-layers model has no block-diffusion objective (the parent
  of the PR that added it) the family ends in ``NoResult``, as it does on a switch the
  program reads one way; the reader finds its scope in ``op_name``s as a compile for the
  v5e writes them, and returns nothing where there is no trace.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_RESILIENCY_LOG_LEVEL", "WARNING")

from benchmark import flops, harness, rehearse  # noqa: E402

CELL = "sdar_30b_a3b_lowlr_noprof"
CONFIG = "sdar-30b-a3b-l6-ep8"
SEEDS = (11, 2147483659, 4000000007)
#: the lists ISSUE 48 names: the two end-to-end metrics and fourteen accepted per-layer
#: metrics, and the one this PR adds
LISTS = ("tokens_per_s", "step_ms_p95", "model.step_device_ms", "model.mfu", "loop.host_ms",
         "loop.overhead", "model.fwd_ms", "model.bwd_ms", "model.opt_ms", "loop.hooks_ms",
         "telemetry.report_ms", "model.attn_ms", "model.moe_ms", "attn.roofline",
         "compile.step_trace_s", "compile.step_load_s", "model.diffuse_ms")


#: (worst loss gap of steps 0-2, first gradient's worst leaf, parameter change's worst leaf)
#: as the harness's own comparison read them on the chip at the configuration's size, both
#: sides on the program's experts (my chip runs, PR 48; PERF.md section 2): a seed's three
#: from ``benchmark/control.py`` in fp8 and in bf16 on 6 seeds, and from the program's sound
#: seeds of the first three steps (``.chip_archive/probe/gaps48.py``) and its full runs
LIMIT_READINGS = {
    "fp8": [(0.00592, 0.03409, 0.00096), (0.00285, 0.0423, 0.0009), (0.00341, 0.04307, 0.00134),
            (0.00427, 0.03874, 0.00336), (0.00353, 0.03346, 0.00065), (0.02592, 0.03136, 0.00237)],
    "bf16": [(0.00018, 0.00263, 0.00013), (0.00033, 0.00315, 0.00006), (0.00038, 0.00142, 0.00023),
             (0.00029, 0.00215, 0.00015), (0.00042, 0.00162, 0.00013), (0.00202, 0.00452, 0.00017)],
    "sound": [
        # 22 seeds of the first three steps, 4800100-4802263 in steps of 103
        (0.00051, 0.00379, 0.00026), (0.00012, 0.01594, 0.00157), (0.00093, 0.00987, 0.00366),
        (0.0007, 0.00928, 0.00066), (0.00055, 0.00743, 0.00171), (0.00058, 0.00992, 0.00203),
        (0.00037, 0.01779, 0.0047), (0.00029, 0.0013, 0.00093), (0.00022, 0.00163, 0.00606),
        (0.00083, 0.00158, 0.00036), (0.00031, 0.00121, 0.0012), (0.00081, 0.00456, 0.00317),
        (0.00021, 0.0072, 0.00132), (0.0004, 0.00715, 0.00233), (0.00024, 0.00586, 0.00144),
        (0.00047, 0.00989, 0.00096), (0.00019, 0.00473, 0.00105), (0.00046, 0.03022, 0.00152),
        (0.00034, 0.00399, 0.00075), (0.00062, 0.00997, 0.00197), (0.0003, 0.00757, 0.00063),
        (0.00185, 0.00269, 0.00437),
        # the tree before it was archived: seeds 4800011, 4800012, and a traced full run (4800001)
        (0.00045, 0.00367, 0.00088), (0.00042, 0.00453, 0.00261), (0.00075, 0.01913, 0.00027),
        # full runs from git archive of the tree: 4803001-4803006 untraced, 4803091 traced
        (0.00017, 0.00129, 0.00044), (0.00044, 0.00833, 0.00036), (0.00043, 0.01187, 0.00046),
        (0.0002, 0.00307, 0.00064), (0.00046, 0.01855, 0.00473), (0.00084, 0.00106, 0.00054),
        (0.00045, 0.0021, 0.00021),
        # a second set of full runs: 4803101-4803106
        (0.0004, 0.00434, 0.00085), (0.00111, 0.00187, 0.00056), (0.00047, 0.00401, 0.00091),
        (0.00034, 0.01013, 0.00056), (0.00057, 0.00406, 0.00119), (0.00035, 0.00688, 0.00121),
    ],
}


@pytest.fixture
def config():
    """Read anew for every test: ``program_config`` leaves the program's ``choices`` in
    the dict it is given, for the reference that gets the same dict."""
    return harness.load_cell(CELL).config


@pytest.mark.parametrize("side", list(LIMIT_READINGS))
def test_the_limits_stand_between_the_sound_readings_and_the_control(config, side):
    """Sound runs and the reference in the stated precision are under all three limits,
    the loss's and the parameter change's with three times of room over the sound runs'
    largest, the gradient's with 1.3 times: the program's first gradient and fp8's all but
    touch (0.0302 against 0.0314), and the limit keeps a sound run correct first. The
    reference in fp8 is over a limit on most seeds read (``limits_why`` says on how many)."""
    limits = config["limits"]
    limit = (limits["loss_abs"], limits["grad_norm_gap"], limits["change_norm_gap"])
    readings = LIMIT_READINGS[side]
    assert len(readings) >= (20 if side == "sound" else 6)
    over = [any(gap > bound for gap, bound in zip(reading, limit)) for reading in readings]
    if side == "fp8":
        assert 2 * sum(over) > len(readings)
        assert f"{sum(over)} of {len(readings)}" in config["limits_why"]["readings"]
        return
    assert not any(over)
    if side == "sound":
        largest = [max(r[i] for r in readings) for i in range(3)]
        assert 3 * largest[0] < limit[0] and 1.3 * largest[1] < limit[1] and 3 * largest[2] < limit[2]


def test_the_program_holds_the_parameters_the_file_counts(config):
    import jax
    import numpy as np

    from tpu_resiliency.models import pattern

    family = harness.load_family(config)
    cfg = family.program_config(config, config["batch"][1])
    described = pattern.describe_params(cfg)
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(  # noqa: E731
        tree, is_leaf=lambda x: isinstance(x, pattern.Leaf)))
    assert count(described) == 645_623_296  # x 12 B = 7.75e9 B of weights and moments
    per_layer = lambda tree: count(tree) // 6  # noqa: E731
    assert per_layer(described["attn"]) == 2 * 8_388_608 + 2 * 1_048_576 + 2 * 128 + 2048
    assert per_layer(described["mlp"]) == 2048 + 262_144 + 75_497_472
    assert per_layer(described["attn"]) + per_layer(described["mlp"]) == 94_638_336
    assert count({k: described[k] for k in ("embed", "lm_head")}) == 77_791_232
    assert "wg" not in described["attn"]["full"]  # no output gate
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)) == 645_623_296
    reference = harness.load_reference(config).describe(config)
    shapes = jax.tree.map(lambda leaf: leaf.shape, described,
                          is_leaf=lambda x: isinstance(x, pattern.Leaf))
    assert shapes == jax.tree.map(lambda leaf: leaf[0], reference,
                                  is_leaf=lambda x: isinstance(x, tuple))
    # five layers, ISSUE 48's fallback had the step not fitted: 550,984,960
    assert 645_623_296 - 94_638_336 == 550_984_960


def test_the_file_states_the_published_config_and_the_cut(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next((r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"), None)
    if published is None:
        pytest.skip("the catalog is not here")
    assert config["source"] == published["source_url"]
    differ = {k for k, v in published["config"].items() if k not in config or config[k] != v}
    assert differ == set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert config["reduced_from"] == {k: published["config"][k] for k in config["reduced"]}
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        6, 16, 18992)
    deployment = config["deployment"]
    assert (deployment["chips"], deployment["chips_per_layer"]) == (64, 8)
    assert deployment["experts_held"] == [0, 16] and deployment["num_experts"] == 128
    assert config["vocab_size"] * deployment["chips_per_layer"] == deployment["vocab_size"] \
        == published["config"]["vocab_size"]
    assert config["diffusion"] == {"block_length": 4, "eps": 0.001, "noise_seed": 0,
                                   "mask_token_id": 18991}
    assert config["optimizer"] == {"lr": 3e-6} and config["batch"] == [1, 4096]
    for key in ("assumed", "departures", "fit", "limits", "limits_why"):
        assert config[key], key
    for key in ("block_length", "noise_schedule", "no_shift", "draws", "mask_token",
                "doubled_stream", "qk_norm", "no_aux_loss", "precision", "optimizer", "init",
                "data"):
        assert config["assumed"][key], key
    assert set(config["limits"]) == {"loss_abs", "grad_norm_gap", "change_norm_gap"}
    assert set(config["limits_why"]) >= {"readings", *config["limits"]}


@pytest.mark.parametrize("size", ["cell", "tiny"])
def test_the_family_meets_the_contract_and_counts_the_least_work(config, size):
    family = harness.load_family(config)
    assert family.REFERENCE == "sdar"
    if size == "cell":
        seq, d = config["batch"][1], config["hidden_size"]
        assert family.routed_share(config) == 1.0  # 8 of 128, 16 held
        assert family.pairs_per_id(config, seq) == 4100.0  # against a causal 8,193
        attention = 2 * d * 32 * 128 + 2 * d * 4 * 128
        assert flops.gqa_projection_params(d, 32, 4, 128) == attention == 18_874_368
        position = attention + d * 128 + 3 * d * 768  # projections, router, one expert's worth
        matmul = 6 * (2 * 6 * position + d * 18992)
        products = 12 * 32 * 128 * 4100 * 6
        assert matmul == pytest.approx(1.951e9, rel=1e-3) and products == pytest.approx(1.209e9, rel=1e-3)
        assert family.train_flops_per_token(config, seq) == pytest.approx(matmul + products)
        total = matmul + products
        assert products / total == pytest.approx(0.38, abs=0.005)
        assert 6 * 2 * 6 * position / total == pytest.approx(0.54, abs=0.005)
        assert 6 * d * 18992 / total == pytest.approx(0.07, abs=0.005)
        ops, moved = family.attention_core_cost(config, 1, seq)
        assert ops == pytest.approx(seq * products)  # 4.95e12: 25 ms at the chip's peak
        assert moved == 6 * 2 * seq * 128 * 2 * (5 * 32 + 6 * 4)
        assert moved / 819e9 < ops / 197e12  # compute-bound
        return
    # at TINY, written out: 3 layers of 8 heads of 16 over 2 KV heads, width 64, experts of
    # 32 of which a position reaches 4 x 4 / 16 = 1, 256 rows of vocabulary, 64 ids in
    # blocks of 4
    tiny = {**config, **family.TINY}
    position = (2 * 64 * 8 * 16 + 2 * 64 * 2 * 16) + 64 * 16 + 1.0 * 3 * 64 * 32
    assert family.routed_share(tiny) == 1.0 and family.pairs_per_id(tiny, 64) == 68.0
    want = 6 * (2 * 3 * position + 64 * 256) + 3 * 12 * 68 * 8 * 16
    assert family.train_flops_per_token(tiny, 64) == pytest.approx(want) == pytest.approx(1_406_976)
    ops, moved = family.attention_core_cost(tiny, 4, 64)
    assert ops == 4 * 64 * 3 * 12 * 68 * 8 * 16 and moved == 3 * 4 * 128 * 16 * 2 * (5 * 8 + 6 * 2)
    # the pairs are the mask's own: the reference's whole mask, counted
    import numpy as np

    mask = np.asarray(harness.load_reference(tiny).stream_mask(64, 4))
    assert mask.sum() == 64 * 68.0


def test_the_cell_is_in_the_lists_the_issue_names_and_the_manifest_only_gained(config):
    manifest = harness.read_json(ROOT, "BENCHMARK.json")
    named = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
             if CELL in m.get("workloads", [])]
    assert sorted(named) == sorted(LISTS)
    assert CELL not in next(m for m in manifest["per_layer"]
                            if m["name"] == "moe.experts_roofline")["workloads"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "steady_no_profiler", "chips": 1}
    assert manifest["workloads"][-1] == cell and manifest["configs"][-1]["name"] == CONFIG
    assert manifest["per_layer"][-1] == {
        "name": "model.diffuse_ms", "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "model", "moves": "tokens_per_s", "workloads": [CELL]}
    entry = manifest["configs"][-1]
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert all(len(x["why"]) <= 200 for x in (cell, entry))
    assert harness.load_cell(CELL).traffic["profile_programs_every"] == 0
    # against the parent's manifest: entries appended, the cell appended to lists, no more
    import subprocess

    shown = subprocess.run(["git", "show", "81456302d2b6b3f7ce28589e037f0f458837cb70:BENCHMARK.json"],
                           cwd=ROOT, capture_output=True, text=True)
    if shown.returncode:
        pytest.skip("no git history here")
    parent = json.loads(shown.stdout)
    assert manifest["configs"][:-1] == parent["configs"]
    assert manifest["workloads"][:-1] == parent["workloads"]
    assert {k: v for k, v in manifest.items() if k not in (
        "configs", "workloads", "end_to_end", "per_layer")} == {
        k: v for k, v in parent.items() if k not in (
            "configs", "workloads", "end_to_end", "per_layer")}
    for was, now in zip(parent["end_to_end"] + parent["per_layer"],
                        manifest["end_to_end"] + manifest["per_layer"][:-1]):
        grew = was["name"] in LISTS
        assert now == ({**was, "workloads": was["workloads"] + [CELL]} if grew else was)


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_and_is_correct(trace):
    run, metrics = rehearse.rehearse(CELL, SEEDS[2], 1.0, trace)
    result = run.result(metrics)
    assert result["correct"] is True, run.problems
    assert len(result["compared"]) == 5
    if not trace:
        assert set(result["metrics"]) == {"tokens_per_s", "step_ms_p95", "setup_s"}
    else:  # the CPU has no device plane: the readers of device time leave their metric out
        assert not {"model.diffuse_ms", "model.attn_ms", "attn.roofline"} & set(result["metrics"])


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


@pytest.mark.parametrize("given", ["own", "the_program's", "shifted_experts"])
def test_the_reference_takes_the_experts_it_is_given_and_refuses_shifted_ones(config, given):
    """``correct`` compares the two sides on the program's experts over the stream of the
    step's own draws. Given its own choices the reference returns its own loss and gradient
    to the bit; given the program's (another arithmetic: a few flips) a loss close by; given
    every expert shifted by one, a loss that is not a number, so that a program that chooses
    wrongly is not followed into ``correct: true``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = {**config, **harness.load_family(config).TINY}
    model = harness.load_reference(cfg)
    params = model.init_params(7, cfg)
    tokens = jnp.asarray(np.random.default_rng(7).integers(0, cfg["vocab_size"], cfg["batch"]), jnp.int32)
    stream = jnp.concatenate([tokens, model.draws(tokens, cfg)[0]], axis=1)
    grad = lambda c: jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, c, "f32")))(params)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        own = jax.jit(lambda p: model.forward(p, stream, cfg, "f32")[1])(params)
        assert own.shape == (3, 4, 128, 4)  # over the stream's 2 x 64 positions
        want, want_grad = grad(cfg)
        if given == "own":
            chose = own
        elif given == "the_program's":
            harness.load_family(cfg).program_config(cfg, cfg["batch"][1])
            chose = cfg.pop("choices")(params, tokens)["experts"]
            differ = float(jnp.mean(jnp.sort(chose, axis=-1) != jnp.sort(own, axis=-1)))
            assert 0 < differ < 0.05  # bfloat16 against float32: a few flips, no other rule
        else:
            chose = (own + 1) % cfg["deployment"]["num_experts"]
        got, got_grad = grad({**cfg, "choices": lambda p, t: {"experts": chose}})
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


def test_a_program_without_the_objective_gives_no_result(config, monkeypatch, capsys):
    from tpu_resiliency.models import pattern

    monkeypatch.delattr(pattern, "Diffusion")
    with pytest.raises(harness.NoResult):
        harness.load_family(config).program_config(config, 64)
    assert "no block-diffusion objective" in capsys.readouterr().err


def test_a_switch_the_program_does_not_compute_gives_no_result(config, capsys):
    family = harness.load_family(config)
    for key, other in (("norm_topk_prob", False), ("decoder_sparse_step", 2),
                       ("mlp_only_layers", [0]), ("use_sliding_window", True),
                       ("sliding_window", 4096), ("tie_word_embeddings", True),
                       ("attention_bias", True), ("hidden_act", "gelu"),
                       ("rope_scaling", {"rope_type": "yarn"}), ("num_experts", 128)):
        with pytest.raises(harness.NoResult):
            family.program_config({**config, key: other}, 64)
        assert key in capsys.readouterr().err
    with pytest.raises(harness.NoResult):  # MASK is the last row of the slice
        family.program_config({**config, "diffusion": {**config["diffusion"], "mask_token_id": 5}}, 64)
    with pytest.raises(harness.NoResult):  # a sequence is whole blocks
        family.program_config(dict(config), 62)
    capsys.readouterr()


def test_the_reader_finds_its_scope_and_returns_nothing_without_a_trace(config):
    scope_times = harness.load_by_path("layer_metrics", "scope_times")
    own = harness.load_by_path("layer_metrics", "model.diffuse_ms").SCOPE
    # names as a compile for the v5e writes them (PR 48)
    draws = "jit(train_step)/jvp(diffuse/noise)/vmap(jit(_uniform))/threefry2x32"
    forward = "jit(train_step)/jvp(diffuse/loss)/reduce_max"
    backward = "jit(train_step)/transpose(jvp(diffuse/loss))/mul"
    products = "jit(train_step)/jvp(attn/full)/core/blocked_attention_fwd"
    for name in (draws, forward, backward):
        assert own.search(name) and scope_times.scopes_of(name, "fusion.1") == []
    assert not own.search(products)
    assert scope_times.scopes_of(products, "custom-call.3") == ["attn", "attn_core"]
    assert not own.search("params['diffuse/noise']")
    cell = harness.load_cell(CELL)
    run = harness.Run(cell, 1, 1.0, True, 0.0, rehearsal=True)
    run.device = {"kind": "TPU v5 lite"}
    try:
        for name in ("model.diffuse_ms", "model.attn_ms", "model.moe_ms", "attn.roofline"):
            assert harness.load_by_path("layer_metrics", name).read(run) is None
        ops, moved = harness.load_family(config).attention_core_cost(config, *config["batch"])
        run.notes["scope_times"] = {"attn": 0.14, "attn_core": 4 * ops / 197e12, "moe": 0.06,
                                    "moe_experts": 0.0}
        read = lambda name: harness.load_by_path("layer_metrics", name).read(run)  # noqa: E731
        assert read("attn.roofline") == pytest.approx(25.0)
        assert read("model.attn_ms") == pytest.approx(140.0)
    finally:
        run.cleanup()
