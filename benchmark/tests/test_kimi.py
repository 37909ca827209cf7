"""Family ``kimi`` and its cell, at the family's tiny widths on the CPU, run by hand
with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- the family meets the contract and its operation count is the least-work sum it says;
- the file states the published config and the cut;
- the cell rehearses traced and untraced with no problem, and ``correct`` is true;
- the control: the reference in fp8 in the program's place fails at least one compared
  number, while the reference in the stated precision (bf16) passes all;
- on a program whose pattern-of-layers model has no latent kind (the parent of the PR
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

CELL = "kimi_vl_a3b_steady_noprof"
SEEDS = (11, 2147483659, 4000000007)
READERS = ("model.attn_ms", "model.moe_ms", "attn.roofline", "attn.latent_ms")


#: (worst loss gap of steps 0-2, first gradient's worst leaf, parameter change's worst leaf)
#: as the harness's own comparison read them on the chip at the configuration's size (PR 32;
#: PERF.md section 2): a seed's three from ``benchmark/control.py`` in fp8 on 18 seeds and in
#: bf16 on 9, and the largest of each over the program's 31 sound seeds
LIMIT_READINGS = {
    "fp8": [(0.00247, 0.00592, 0.00118), (0.00752, 0.00769, 0.00132), (0.00746, 0.00805, 0.00073),
            (0.00428, 0.00335, 0.00171), (0.00651, 0.00568, 0.00117), (0.00662, 0.02638, 0.00125),
            (0.00489, 0.00460, 0.00266), (0.00354, 0.01484, 0.00275), (0.00707, 0.00762, 0.00313),
            (0.00671, 0.00672, 0.00077), (0.00713, 0.00655, 0.00073), (0.00430, 0.00176, 0.00233),
            (0.00723, 0.00776, 0.00485), (0.00194, 0.00849, 0.00269), (0.00469, 0.00730, 0.00095),
            (0.00335, 0.00527, 0.00443), (0.00869, 0.01018, 0.00092), (0.00610, 0.01324, 0.00124)],
    "bf16": [(0.00194, 0.00165, 0.00062), (0.00047, 0.00145, 0.00105), (0.00078, 0.00112, 0.00025),
             (0.00184, 0.00185, 0.00055), (0.00138, 0.00144, 0.00035), (0.00102, 0.00079, 0.00017),
             (0.00164, 0.00160, 0.00034), (0.00283, 0.00154, 0.00020), (0.00073, 0.00217, 0.00026)],
    "sound": [(0.00414, 0.00436, 0.00122)],
}


@pytest.mark.parametrize("side", list(LIMIT_READINGS))
def test_the_limits_stand_over_the_sound_readings_and_under_most_of_the_control(config, side):
    """The stated precision and the sound runs are under all three limits: the sound runs'
    largest loss with three times of room (the loss cannot tell fp8 from a flipped router
    choice), their largest gradient and parameter change with half as much again. The
    gradient limit is under the control's median, and fp8 is over a limit on 15 seeds of
    18: the three it is not are readings inside the sound runs' range (0.0034-0.0059
    against 0.0044), which no limit separates."""
    limits = config["limits"]
    limit = (limits["loss_abs"], limits["grad_norm_gap"], limits["change_norm_gap"])
    over = [[gap > bound for gap, bound in zip(reading, limit)] for reading in LIMIT_READINGS[side]]
    if side == "fp8":
        gradients = sorted(reading[1] for reading in LIMIT_READINGS[side])
        assert limit[1] < gradients[len(gradients) // 2]
        assert sum(any(row) for row in over) == 15
        assert [r[1] for r, row in zip(LIMIT_READINGS[side], over) if not any(row)] == [
            0.00592, 0.00335, 0.00568]
    else:
        assert not any(any(row) for row in over), (LIMIT_READINGS[side], limit)
    if side == "sound":
        (loss, gradient, change), = LIMIT_READINGS[side]
        assert 3 * loss < limit[0] and 1.45 * gradient < limit[1] and 1.45 * change < limit[2]


@pytest.fixture(scope="module")
def config():
    return harness.load_cell(CELL).config


def test_the_family_meets_the_contract_and_counts_the_least_work(config):
    family = harness.load_family(config)
    assert family.REFERENCE == "kimi"
    assert family.mlp_kinds(config) == ["dense"] + ["sparse"] * 5
    seq = config["batch"][1]
    d = config["hidden_size"]
    assert family.routed_share(config) == 0.75  # 6 of 64, 8 held
    # the count, written out: the four latent matrices, the MLPs, the head, the products
    attention = d * 16 * 192 + d * (512 + 64) + 512 * 16 * (128 + 128) + 16 * 128 * d
    assert family.latent_projection_params(config) == attention == 13_762_560
    sparse = d * 64 + 3 * d * 2816 + 0.75 * 3 * d * 1408
    matmul = 6 * attention + 3 * d * 11264 + 5 * sparse + d * 20480
    products = 6 * (seq / 2) * 16 * (192 + 128)
    assert family.train_flops_per_token(config, seq) == pytest.approx(6 * matmul + 6 * products)
    assert family.train_flops_per_token(config, seq) == pytest.approx(2.635e9, rel=1e-3)
    ops, moved = family.attention_core_cost(config, 1, seq)
    assert ops == pytest.approx(6 * seq * products)
    # q and the keys at the score width with the rotary key once, v and the output at 128
    q, k, v = 16 * 192, 16 * 128 + 64, 16 * 128
    assert moved == 6 * seq * 2 * (3 * q + 3 * k + 6 * v)
    ops, moved = family.expert_products_cost(config, 1, seq)
    assert ops == pytest.approx(5 * 0.75 * seq * 6 * flops.swiglu_params(d, 1408))
    assert moved > 5 * 2 * 3 * 8 * flops.swiglu_params(d, 1408)


def test_the_file_states_the_published_config_and_the_cut(config):
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next((r for r in rows if r["name"] == "Kimi-VL-A3B-Instruct"), None)
    if published is None:
        pytest.skip("the catalog is not here")
    assert config["source"] == published["source_url"]
    differ = {k for k, v in published["config"].items() if k not in config or config[k] != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["reduced_from"] == {k: published["config"][k] for k in config["reduced"]}
    assert config["deployment"]["experts_held"] == [0, config["n_routed_experts"]]
    assert config["deployment"]["n_routed_experts"] == published["config"]["n_routed_experts"]
    assert config["vocab_size"] * config["deployment"]["chips_per_layer"] \
        == config["deployment"]["vocab_size"] == published["config"]["vocab_size"]
    for key in ("assumed", "departures", "fit", "limits", "limits_why"):
        assert config[key], key
    assert config["batch"] == [1, 8192]


def test_the_program_holds_the_parameters_the_file_counts(config):
    import jax
    import numpy as np

    from tpu_resiliency.models import pattern

    cfg = harness.load_family(config).program_config(config, config["batch"][1])
    leaves = jax.tree.leaves(pattern.describe_params(cfg),
                             is_leaf=lambda x: isinstance(x, pattern.Leaf))
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == 668_890_432
    reference = harness.load_reference(config).describe(config)
    shapes = jax.tree.map(lambda leaf: leaf.shape, pattern.describe_params(cfg),
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
    # the selection bias's gradient is the balancing rule's: +-1 an expert, on every side
    bias = "['mlp']['sparse']['b_router']"
    entries = 2 * cfg["deployment"]["n_routed_experts"]  # two sparse layers
    assert reference["grad_norms"][bias] == stated["grad_norms"][bias] == pytest.approx(
        entries ** 0.5)
    assert reference["change_norms"][bias] > 0


def test_a_program_without_the_latent_kind_gives_no_result(config, monkeypatch, capsys):
    from tpu_resiliency.models import pattern

    monkeypatch.delattr(pattern, "Latent")
    with pytest.raises(harness.NoResult):
        harness.load_family(config).program_config(config, 64)
    assert "no latent attention" in capsys.readouterr().err


def test_a_switch_the_program_does_not_compute_gives_no_result(config, capsys):
    family = harness.load_family(config)
    for key, other in (("q_lora_rank", 1536), ("scoring_func", "softmax"), ("n_group", 8),
                       ("rope_scaling", {"type": "yarn"}), ("norm_topk_prob", False)):
        with pytest.raises(harness.NoResult):
            family.program_config({**config, key: other}, 64)
        assert key in capsys.readouterr().err


def test_the_readers_find_the_scopes_and_return_nothing_without_a_trace(config):
    scope_times = harness.load_by_path("layer_metrics", "scope_times")
    latent = harness.load_by_path("layer_metrics", "attn.latent_ms").SCOPE
    # names as a compile for the v5e writes them (PR 32)
    forward = "jit(train_step)/jvp(attn/full)/latent/dot_general"
    backward = ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
                "attn/full/latent/mul")
    kernel = "jit(train_step)/jvp(attn/full)/core/blocked_attention_fwd/pallas_call"
    for name in (forward, backward):
        assert latent.search(name) and scope_times.scopes_of(name, "fusion.1") == ["attn"]
    assert scope_times.scopes_of(kernel, "custom-call.3") == ["attn", "attn_core"]
    assert not latent.search(kernel)
    assert not latent.search("jit(train_step)/jvp(attn/full)/dot_general")  # W_o, the norm
    assert not latent.search("params['attn']['latent']['wq']")
    cell = harness.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} >= set(READERS)
    run = harness.Run(cell, 1, 1.0, True, 0.0, rehearsal=True)
    run.device = {"kind": "TPU v5 lite"}
    try:
        for name in READERS:
            assert harness.load_by_path("layer_metrics", name).read(run) is None
        ops, moved = harness.load_family(config).attention_core_cost(config, *config["batch"])
        run.notes["scope_times"] = {"attn": 0.15, "attn_core": 4 * ops / 197e12, "moe": 0.08,
                                    "moe_experts": 0.0}
        read = lambda name: harness.load_by_path("layer_metrics", name).read(run)  # noqa: E731
        assert moved / 819e9 < ops / 197e12  # the attention products are compute-bound
        assert read("attn.roofline") == pytest.approx(25.0)
        assert read("model.attn_ms") == pytest.approx(150.0)
        assert read("model.moe_ms") == pytest.approx(80.0)
        # the router leaves the held experts on this traffic: no share of a roofline is
        # listed for products that the cell runs nearly empty
        assert "moe.experts_roofline" not in {m["name"] for m in cell.per_layer}
    finally:
        run.cleanup()
