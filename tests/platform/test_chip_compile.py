"""Compile the main path's kernels and programs for a v5e chip that is described,
not attached: what Mosaic or the TPU compiler would refuse on the chip (a slice off
the tiling, too much VMEM, a program that does not fit HBM) is refused here, at no
chip time. Nothing runs, so this says nothing about results or speed.

The topology is described inside a module-scoped fixture and nowhere else: only one
process may load the TPU's library, the suite runs under several xdist workers, and
every worker imports this file. All cases stay in THIS file so one worker owns them.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

R, S = 4096, 64  # the north-star telemetry width (BASELINE.json)
HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` sees the CPU here and takes its
    CPU branch (interpret mode, the XLA sort): steer it from the test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def windows(w, sharding):
    return sds((R, S, w), jnp.float32, sharding), sds((R, S), jnp.int32, sharding)


@pytest.mark.parametrize("window", [32, 128], ids=["loop-W32", "loop-W128"])
def test_median_kernel_compiles_for_v5e(one_chip, window):
    from tpu_resiliency.ops.scoring_pallas import fused_median_weights

    data, counts = windows(window, one_chip)
    compiled = fused_median_weights.lower(
        data, counts, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_score_program_compiles_for_v5e(one_chip):
    """The whole scoring round: the kernel plus the cross-rank scoring math."""
    from tpu_resiliency.ops.scoring_pallas import fused_median_weights
    from tpu_resiliency.telemetry import scoring

    def score_program(d, c, e, h):
        mw = fused_median_weights(d, c, interpret=False)
        return scoring.score_round(d, c, e, h, medians_and_weights=mw)

    data, counts = windows(32, one_chip)
    compiled = jax.jit(score_program).lower(
        data, counts, sds((R,), jnp.float32, one_chip), sds((R, S), jnp.float32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def telemetry_state(mesh, window):
    from tpu_resiliency.telemetry.sharded import TelemetryState

    rows = NamedSharding(mesh, P("rank"))
    return TelemetryState(
        data=sds((window, R, S), jnp.float32, NamedSharding(mesh, P(None, "rank"))),
        counts=sds((R, S), jnp.int32, rows),
        cursor=sds((), jnp.int32, NamedSharding(mesh, P())),
        ewma=sds((R,), jnp.float32, rows),
        hist_min=sds((R, S), jnp.float32, rows),
    )


def test_ring_push_compiles_for_v5e(topo):
    from tpu_resiliency.telemetry.sharded import MeshTelemetry

    mesh = Mesh(np.asarray(topo.devices[:1]), ("rank",))
    mt = MeshTelemetry(mesh, "rank", n_ranks=R, window=32, use_pallas=False,
                       signal_names=tuple(f"s{j}" for j in range(S)))
    compiled = mt._push.lower(
        telemetry_state(mesh, 32), sds((R, S), jnp.float32, NamedSharding(mesh, P("rank")))
    ).compile()
    # The donated ring is updated in place: the program holds no second copy.
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 32 * R * S * 4


def test_sharded_scorer_compiles_for_four_chips_with_the_kernel(topo, as_on_a_tpu):
    """4096 ranks over four chips: the kernel runs per shard inside shard_map, the
    cross-rank reductions are collectives, and auto-selection picks the kernel."""
    from tpu_resiliency.telemetry.sharded import MeshTelemetry

    mesh = Mesh(np.asarray(topo.devices), ("rank",))
    mt = MeshTelemetry(mesh, "rank", n_ranks=R, window=32,
                       signal_names=tuple(f"s{j}" for j in range(S)))
    assert mt.use_pallas is True  # what use_pallas=None resolves to on a TPU
    text = mt._score_reset.lower(telemetry_state(mesh, 32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "all-reduce" in text


def flagship():
    import chip_smoke
    from tpu_resiliency.models import transformer as tfm

    cfg = chip_smoke.model_config(tiny=False)
    train_step, init_opt = tfm.make_train_step(cfg)
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(init_opt, params)
    size = chip_smoke.SIZES["full"]
    return cfg, train_step, init_opt, params, opt, (size["batch"], size["seq"])


def placed(tree, shardings):
    return jax.tree.map(lambda x, s: sds(x.shape, x.dtype, s), tree, shardings)


def test_flagship_train_step_fits_one_chip(one_chip):
    """8L x 1024d, batch 8 x seq 1024, AdamW, donated: it fits alone, and not
    beside a second copy of its 1.9 GB state."""
    _, train_step, _, params, opt, batch = flagship()
    on_chip = lambda tree: placed(tree, jax.tree.map(lambda _: one_chip, tree))  # noqa: E731
    compiled = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt), sds(batch, jnp.int32, one_chip)
    ).compile()
    mem = compiled.memory_analysis()
    state = mem.argument_size_in_bytes
    assert 1.8e9 < state < 2.0e9
    assert state + mem.temp_size_in_bytes < HBM_BYTES
    assert 2 * state + mem.temp_size_in_bytes > HBM_BYTES


def test_flagship_train_step_compiles_for_the_dp_tp_mesh(topo):
    """What ``chip_smoke.py --chips 4`` runs: dp=2 x tp=2 on the 2x2 host."""
    from tpu_resiliency.parallel import mesh as pmesh

    cfg, train_step, init_opt, params, opt, batch = flagship()
    mesh = pmesh.build_mesh(devices=topo.devices, **pmesh.default_split(4))
    pshard = pmesh.tree_shardings(mesh, pmesh.param_specs(cfg))
    oshard = pmesh.opt_state_shardings(init_opt, params, pshard)
    compiled = jax.jit(
        train_step, donate_argnums=(0, 1), out_shardings=(pshard, oshard, None)
    ).lower(
        placed(params, pshard), placed(opt, oshard),
        sds(batch, jnp.int32, NamedSharding(mesh, pmesh.batch_spec())),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES  # per chip
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("kind", ["full", "sliding"])
def test_attention_kernels_compile_for_v5e_under_the_core_scope(one_chip, as_on_a_tpu, kind):
    """One attention sublayer of the laguna configuration at its own widths (8,192
    tokens, heads of 128, 48 or 64 query heads over 8 KV heads), differentiated through
    a ``jax.checkpoint`` that keeps nothing: Mosaic takes the three kernels (forward,
    recomputed forward, backward), and in the compiled program each is a custom call whose
    ``op_name`` the benchmark's ``attn.roofline`` reader finds under
    ``attn/<kind>/core``."""
    import re

    from benchmark import harness
    from tpu_resiliency.models import pattern

    config = harness.read_json(harness.HERE, "configs", "laguna-xs2-l5-ep8.json")
    seq = config["batch"][1]
    cfg = harness.load_family(config).program_config(config, seq)
    assert pattern.attention_paths(cfg, seq)[kind]["path"] == "kernel"
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda w: sds(w.shape[1:], w.dtype, one_chip), params["attn"][kind])
    tables = pattern.rope_tables(cfg.rope(kind), cfg.head_dim, seq)

    def loss(x, lp):
        layer = jax.checkpoint(lambda x, lp: pattern._attn_block(cfg, kind, x, lp, *tables))
        return jnp.sum(layer(x, lp).astype(jnp.float32) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        sds((1, seq, cfg.d_model), cfg.dtype, one_chip), lp).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in kernels]
    mark = harness.load_by_path("layer_metrics", "scope_times").SCOPES["attn_core"]
    assert len(names) == 3 and all(mark.search(name) for name in names), names


def test_routed_layer_compiles_for_v5e_with_both_widths_under_one_conditional(one_chip):
    """One sparse layer's routed part at the laguna configuration's own widths (8,192
    tokens, 8 choices of 256 experts, 32 held), differentiated through the layer's
    ``jax.checkpoint``: the bounded dispatch (16,384 rows) and the full width (65,536)
    are the two branches of conditionals, the grouped products exist at both widths,
    and what the backward pass keeps alive fits beside the cell's 8.3 GB of state."""
    import re

    from benchmark import harness
    from tpu_resiliency.models import pattern

    config = harness.read_json(harness.HERE, "configs", "laguna-xs2-l5-ep8.json")
    tokens = config["batch"][0] * config["batch"][1]
    cfg = harness.load_family(config).program_config(config, config["batch"][1])
    assert pattern.dispatch_rows(cfg, tokens) == {
        "path": "bounded", "rows": 16384, "pairs": 65536}
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda w: sds(w.shape[1:], w.dtype, one_chip), params["mlp"]["sparse"])

    def loss(y, lp):
        layer = jax.checkpoint(lambda y, lp: pattern.routed_experts(cfg, y, lp)[0])
        return jnp.sum(layer(y, lp).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        sds((tokens, cfg.d_model), cfg.dtype, one_chip), lp).compile()
    text = compiled.as_text()
    assert " conditional(" in text
    grouped = set(re.findall(r"ragged-dot[\w.\-]* = bf16\[(\d+),", text))
    assert {"16384", "65536"} <= grouped, grouped
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9  # 1.3e9 (compile, PR 31)


def cell_config(name: str):
    """(the benchmark's configuration file, the program's configuration at its batch)."""
    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", f"{name}.json")
    return config, harness.load_family(config).program_config(config, config["batch"][1])


def test_latent_attention_compiles_for_v5e_on_the_blocks_with_its_scopes(one_chip, as_on_a_tpu):
    """One latent attention sublayer at the kimi configuration's own widths (8,192
    tokens, 16 heads, scores 192 wide over values of 128, a latent of 512),
    differentiated through the layer's ``jax.checkpoint``: the kernels take one width, so
    the products go by the ``jax.numpy`` blocks, no custom call, their ops under
    ``attn/full/core`` where ``attn.roofline`` looks, and ops remain under
    ``attn/full/latent`` for ``attn.latent_ms``."""
    import re

    from benchmark import harness
    from tpu_resiliency.models import pattern

    config, cfg = cell_config("kimi-vl-a3b-l6-ep8")
    seq = config["batch"][1]
    assert pattern.attention_paths(cfg, seq) == {"latent": {
        "path": "blocks", "block": 1024, "score_width": 192, "value_width": 128}}
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda w: sds(w.shape[1:], w.dtype, one_chip), params["attn"]["latent"])
    tables = pattern.rope_tables(cfg.rope_latent, cfg.latent.d_rope, seq)

    def loss(x, lp):
        layer = jax.checkpoint(lambda x, lp: pattern._latent_block(cfg, x, lp, *tables))
        return jnp.sum(layer(x, lp).astype(jnp.float32) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        sds((1, seq, cfg.d_model), cfg.dtype, one_chip), lp).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    core = harness.load_by_path("layer_metrics", "scope_times").SCOPES["attn_core"]
    latent = harness.load_by_path("layer_metrics", "attn.latent_ms").SCOPE
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert sum(1 for name in names if core.search(name)) >= 8
    assert sum(1 for name in names if latent.search(name)) >= 8


def test_indexed_attention_compiles_for_v5e_on_the_kernels_with_its_scopes(one_chip, as_on_a_tpu):
    """One indexed attention sublayer at the keye configuration's own widths (8,192 tokens,
    32 heads over 4 KV heads of 128, an indexer of 16 heads of 64 that keeps 2,048 keys),
    differentiated through the layer's ``jax.checkpoint`` with the selection, the
    attention output and its log-sum-exp, the indexer's target and its scores kept: the
    products go by the blocked kernels with the selection as an operand, three custom calls
    (forward, the summed probabilities, backward: the target is kept, so its kernel runs
    once), every one under ``attn/full/core`` where ``attn.roofline`` looks; the index
    scores by the kernels of ``ops/index_scores.py``, for each of the four groups of query
    rows the forward kernel (once: the scores are kept), ``dqi`` with ``dwi``, and ``dki``,
    every one under ``attn/full/indexer``; ops under ``attn/full/indexer`` and
    ``attn/full/select`` where the two readers of PR 35 do, forward and backward, and the
    counting passes of the selection in the first forward alone."""
    import re

    from benchmark import harness
    from tpu_resiliency.models import pattern

    config, cfg = cell_config("keye-vl2-30b-a3b-l6-ep8")
    seq = config["batch"][1]
    assert pattern.attention_paths(cfg, seq) == {"indexed": {
        "path": "kernel", "tile": 512, "selected": 2048, "selection": "mask", "scores": "kernel"}}
    assert pattern.key_groups(seq, 512) == [(0, 2048), (2048, 4096), (4096, 6144), (6144, 8192)]
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda w: sds(w.shape[1:], w.dtype, one_chip), params["attn"]["indexed"])
    tables = (pattern.rope_tables(cfg.rope_indexed, cfg.head_dim, seq)
              + pattern.rope_tables(cfg.rope_indexed, cfg.indexer.head_dim, seq))
    policy = jax.checkpoint_policies.save_only_these_names(*(name for group in (
        "selection", "attention", "target", "scores") for name in pattern.KEPT_GROUPS[group]))

    def loss(x, lp):
        layer = jax.checkpoint(lambda x, lp: pattern._indexed_block(cfg, x, lp, *tables)[:2],
                               policy=policy)
        out, counts = layer(x, lp)
        return jnp.sum(out.astype(jnp.float32) ** 2) + counts["index_kl"]

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        sds((1, seq, cfg.d_model), cfg.dtype, one_chip), lp).compile()
    text = compiled.as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    core = harness.load_by_path("layer_metrics", "scope_times").SCOPES["attn_core"]
    own = harness.load_by_path("layer_metrics", "attn.indexer_ms").SCOPES
    kernels = [re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(name.rsplit("/", 2)[-2] for name in kernels) == [
        "blocked_attention_bwd", "blocked_attention_fwd", "blocked_attention_probs",
        *["index_scores_dk"] * 4, *["index_scores_dq"] * 4, *["index_scores_fwd"] * 4], kernels
    products = [name for name in kernels if "blocked_attention" in name]
    assert all(core.search(name) for name in products), products
    scores = [name for name in kernels if "index_scores" in name]
    assert all(own["indexer"].search(name) and not core.search(name) for name in scores), scores
    for scope, mark in own.items():
        under = [name for name in names if mark.search(name)]
        assert len(under) >= 8, scope
        assert any("jvp(" in name for name in under), scope
        # the mask is kept: the backward pass holds nothing of the selection but its reading
        assert any("transpose(" in name for name in under) == (scope == "indexer"), scope
    # the float32 target of one layer and its cotangent's terms, not the blocks' scores
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def pattern_step(config, cfg, one_chip):
    """The whole donating step of a ``models/pattern.py`` configuration at its cell's
    batch, compiled for one described chip, every layer keeping what
    ``pattern.kept_residuals`` gives where no memory limit is stated (here as on the
    chip at these shapes: the tier-1 tests pin that): (compiled, parameters, bytes the
    step needs)."""
    from tpu_resiliency.models import pattern

    train_step, init_opt = pattern.make_train_step(cfg)
    params = jax.eval_shape(lambda: pattern.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(init_opt, params)
    on_chip = lambda tree: placed(tree, jax.tree.map(lambda _: one_chip, tree))  # noqa: E731
    compiled = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt), sds(tuple(config["batch"]), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    needed = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
              + mem.generated_code_size_in_bytes)
    return compiled, sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)), needed


def test_kimi_train_step_fits_one_chip(one_chip, as_on_a_tpu):
    """The whole donating step of ``kimi-vl-a3b-l6-ep8`` at 1 x 8192, attention on the blocks:
    668,890,432 parameters, 8.03e9 B of f32 weights and AdamW moments, and what the step
    needs beside them inside one v5e's 15.75 GiB, with every group of residuals kept."""
    compiled, n_params, needed = pattern_step(*cell_config("kimi-vl-a3b-l6-ep8"), one_chip)
    assert n_params == 668_890_432
    assert 8.0e9 < compiled.memory_analysis().argument_size_in_bytes < 8.1e9
    # 14.94e9 (compile, PR 33; 15.06e9 with nothing kept, PR 32)
    assert needed < 15.75 * 2 ** 30, needed


def test_laguna_train_step_fits_one_chip_and_runs_each_forward_kernel_once(one_chip, as_on_a_tpu):
    """The whole donating step of ``laguna-xs2-l5-ep8`` at 1 x 8192, attention on the
    kernels: 691,623,936 parameters, 8.30e9 B of state, every group of residuals kept
    inside one v5e's 15.75 GiB; and the forward attention kernel is called once a layer,
    5 times and not 10: its output and its log-sum-exp are kept, so the backward pass
    does not run it again."""
    compiled, n_params, needed = pattern_step(*cell_config("laguna-xs2-l5-ep8"), one_chip)
    assert n_params == 691_623_936
    assert 8.2e9 < compiled.memory_analysis().argument_size_in_bytes < 8.4e9
    assert needed < 15.75 * 2 ** 30, needed  # 13.64e9 (compile, PR 33; 12.10e9 with nothing kept)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = {name: sum(1 for line in calls if f"blocked_attention_{name}" in line)
               for name in ("fwd", "bwd")}
    assert kernels == {"fwd": 5, "bwd": 5}, kernels  # PR 49: one backward kernel (dq 5, dkv 5)


@pytest.mark.slow  # 65-210 s of compilation on every core: run by hand, with the chip's own check
def test_keye_train_step_fits_one_chip(one_chip, as_on_a_tpu, monkeypatch):
    """The whole donating step of ``keye-vl2-30b-a3b-l6-ep8`` at 1 x 8192, attention under
    the indexer's selection on the kernels: 659,189,632 parameters, 7.91e9 B of f32 weights
    and AdamW moments, and what the step needs beside them inside one v5e's 15.75 GiB, with
    the eight groups of residuals that ``kept_residuals`` gives at the chip's memory (stated
    here, where the CPU states none), the selection, the indexer's target (the groups' rows
    of the kernel's ``[1, 8192, 8192]`` float32 square) and its scores among them; a layer
    runs the forward, the summed probabilities and the backward kernel once each, and for each
    of its four groups of query rows the index-score kernels once each: forward, ``dqi``
    and ``dki``. The tier-1 run has the one indexed layer above."""
    from tpu_resiliency.models import pattern

    limit = 16_909_336_064  # memory_stats()["bytes_limit"] on the chip
    monkeypatch.setattr(pattern, "device_memory_bytes", lambda: limit)
    config, cfg = cell_config("keye-vl2-30b-a3b-l6-ep8")
    kept = pattern.kept_residuals(cfg, 8192, limit, 8192)
    assert list(kept["per_layer"]) == [
        "routing", "selection", "stream", "attention", "qkv", "index", "target", "scores"]
    compiled, n_params, needed = pattern_step(config, cfg, one_chip)
    assert n_params == 659_189_632
    assert 7.9e9 < compiled.memory_analysis().argument_size_in_bytes < 8.0e9
    # 15.41e9 (compile, PR 46: 15,410,194,944 B with 2.01e9 B of target and scores kept;
    # 15.73e9 with the target kept as the kernel's whole square, 2.62e9 B; 13.67e9 at the
    # parent by the same recipe, of it 0.35e9 of code where this step has 0.08e9; 13.70e9
    # (compile, PR 38); 13.85e9 with the index scores on the blocks, PR 36)
    assert needed < 15.75 * 2 ** 30, needed
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = {name: sum(1 for line in calls if f"blocked_attention_{name}" in line)
               for name in ("fwd", "probs", "bwd")}
    assert kernels == {"fwd": 6, "probs": 6, "bwd": 6}, kernels  # PR 49: dq 6, dkv 6 before
    scores = {name: sum(1 for line in calls if f"index_scores_{name}/" in line)
              for name in ("fwd", "dq", "dk")}
    assert scores == {"fwd": 24, "dq": 24, "dk": 24}, scores


def test_solar_train_step_fits_one_chip(one_chip, as_on_a_tpu, monkeypatch):
    """The whole donating step of ``solar-open2-250b-l4-ep40-tp8`` at 1 x 8192: 840,871,320
    parameters, 10.09e9 B of f32 weights and AdamW moments (60% of the chip before any
    activation), the softmax layer on the kernels (8 heads over the one KV head held: each
    kernel once), the three delta layers' rule in ``jax.numpy`` by chunks of 64 with each
    chunk's system inverted by blocks (matrix products: no triangular solve in the step),
    and what the step needs beside its state inside one v5e's 15.75 GiB with the six groups
    of residuals that ``kept_residuals`` gives at that memory (stated here, where the CPU
    states none: with no limit the shared expert's products would be kept too)."""
    from tpu_resiliency.models import pattern

    limit = int(15.75 * 2 ** 30)
    monkeypatch.setattr(pattern, "device_memory_bytes", lambda: limit)
    config, cfg = cell_config("solar-open2-250b-l4-ep40-tp8")
    kept = pattern.kept_residuals(cfg, 8192, limit, 8192)
    assert list(kept["per_layer"]) == ["routing", "stream", "attention", "states", "qkv", "delta"]
    assert pattern.attention_paths(cfg, 8192) == {
        "full": {"path": "kernel", "tile": 512},
        "delta": {"path": "chunks", "chunk": 64, "solve": "blocks"}}
    compiled, n_params, needed = pattern_step(config, cfg, one_chip)
    assert n_params == 840_871_320
    assert 10.0e9 < compiled.memory_analysis().argument_size_in_bytes < 10.2e9
    # 16.224e9 (compile, PR 45: the Gram matrices by sub-blocks; 16.214e9 at its parent by the
    # same recipe, recorded as 16.159e9 by PR 40; 16.15e9 with XLA's triangular solve, PR 39;
    # 15.95e9 with four groups kept)
    assert needed < limit, needed
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = {name: sum(1 for line in calls if f"blocked_attention_{name}" in line)
               for name in ("fwd", "bwd")}
    assert kernels == {"fwd": 1, "bwd": 1}, kernels  # PR 49: one backward kernel
    # no [chunk, chunk, d_key] array of decay differences is written out: they live inside
    # the fusions that sum over them (what ``pattern._rule_bytes`` leaves out)
    import re
    assert not re.search(r"= f32\[1,8,8,64,64,128\]\S* fusion\(", text)
    # nor does a chunk's system go to XLA's triangular solve (46 ms a step of unfused
    # custom calls on 64 x 64 systems until PR 40): no such op, whatever it lowers to
    assert not re.search(r"triangular[_-]?solve", text, re.IGNORECASE)


def test_ouro_train_step_fits_one_chip(one_chip, as_on_a_tpu, monkeypatch):
    """The whole donating step of ``ouro-2.6b-l8`` at 1 x 4096 through the dense model
    (``models/transformer.py``): 612,438,017 parameters, 7.35e9 B of f32 weights and AdamW
    moments, eight layers run four times under a scan over the passes around the scan over
    the layers (so each attention kernel is in the program once), the products on the
    kernels at one query head a KV head with no ``[B, H, T, T]`` array anywhere, each layer
    of each pass rematerialized with the four groups that ``kept_residuals`` gives at the
    chip's memory (stated here, where the CPU states none), and a peak inside one v5e's
    15.75 GiB."""
    import re

    from benchmark import harness
    from tpu_resiliency.models import transformer as tfm

    limit = 16_909_336_064  # memory_stats()["bytes_limit"] on the chip
    monkeypatch.setattr(tfm, "device_memory_bytes", lambda: limit)
    config = harness.read_json(harness.HERE, "configs", "ouro-2.6b-l8.json")
    family = harness.load_family(config)
    batch, seq = config["batch"]
    cfg = family.program_config(config, seq)
    assert tfm.attention_path(cfg, seq) == {"path": "kernel", "tile": 512}
    kept = tfm.kept_residuals(cfg, batch * seq, limit, seq)
    assert list(kept["groups"]) == ["attention", "mlp_proj", "attn_proj", "v"]
    train_step, init_opt = family.make_train_step(cfg)
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)) == 612_438_017
    opt = jax.eval_shape(init_opt, params)
    on_chip = lambda tree: placed(tree, jax.tree.map(lambda _: one_chip, tree))  # noqa: E731
    compiled = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt), sds((batch, seq), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert 7.3e9 < mem.argument_size_in_bytes < 7.4e9
    # 16.13e9 (compile, PR 43; 13.46e9 with nothing kept, 0.67e9 a group; a fifth group is
    # refused). ``temp_size_in_bytes`` counts the loops' buffers more than once here
    assert mem.peak_memory_in_bytes < limit - tfm.RESERVED_BYTES, mem.peak_memory_in_bytes
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = {name: sum(1 for line in calls if f"blocked_attention_{name}" in line)
               for name in ("fwd", "bwd")}
    assert kernels == {"fwd": 1, "bwd": 1}, kernels  # PR 49: one backward kernel
    assert not re.search(r"\[1,16,4096,4096\]", text)
    scopes = harness.load_by_path("layer_metrics", "model.exit_ms").SCOPES
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope, mark in scopes.items():  # what the two new readers look for is there
        assert sum(1 for name in names if mark.search(name)) > 10, scope


def test_sdar_train_step_fits_one_chip_and_walks_the_doubled_stream_on_the_kernels(
        one_chip, as_on_a_tpu, monkeypatch):
    """The whole donating step of ``sdar-30b-a3b-l6-ep8`` at 1 x 4096 ids, a stream of 8,192
    positions ``[clean ; noised]`` through every layer: 645,623,296 parameters, 7.75e9 B of
    f32 weights and AdamW moments, AdamW at the file's 3e-6, the four groups of residuals
    that ``kept_residuals`` gives at the chip's memory (stated here, where the CPU states
    none) and what the step needs beside its state inside the chip's ``bytes_limit``; a
    layer runs the forward and the backward kernel of the ``noised`` form once each, every one
    under ``attn/full/core`` where ``attn.roofline`` looks; the mask is no array: no
    ``[8192, 8192]`` value of any type is in the program; and ops stand under ``diffuse/``
    where ``model.diffuse_ms`` looks."""
    import re

    from benchmark import harness
    from tpu_resiliency.models import pattern

    limit = 16_909_336_064  # memory_stats()["bytes_limit"] on the chip
    monkeypatch.setattr(pattern, "device_memory_bytes", lambda: limit)
    config, cfg = cell_config("sdar-30b-a3b-l6-ep8")
    batch, ids = config["batch"]
    assert pattern.attention_paths(cfg, cfg.stream(ids)) == {"full": {
        "path": "kernel", "tile": 512, "walk": "noised", "block_length": 4, "clean": 4096}}
    kept = pattern.kept_residuals(cfg, cfg.stream(batch * ids), limit, cfg.stream(ids))
    assert list(kept["per_layer"]) == ["routing", "stream", "attention", "qkv"]
    family = harness.load_family(config)
    train_step, init_opt = family.make_train_step(cfg, optimizer=config["optimizer"])
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)) == 645_623_296
    opt = jax.eval_shape(init_opt, params)
    on_chip = lambda tree: placed(tree, jax.tree.map(lambda _: one_chip, tree))  # noqa: E731
    compiled = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt), sds((batch, ids), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert 7.7e9 < mem.argument_size_in_bytes < 7.8e9
    # 12.50e9 (compile, PR 48: 7.75e9 of arguments + 4.54e9 of temporaries + 0.21e9 of code)
    needed = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
              + mem.generated_code_size_in_bytes)
    assert needed < limit, needed
    text = compiled.as_text()
    calls = [re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and "blocked_attention" in line]
    kernels = {name: sum(1 for call in calls if f"blocked_attention_{name}" in call)
               for name in ("fwd", "bwd")}
    # PR 49: one backward kernel a layer (fwd 6, dq 6, dkv 6: 18 before)
    assert kernels == {"fwd": 6, "bwd": 6} and len(calls) == 12, kernels
    core = harness.load_by_path("layer_metrics", "scope_times").SCOPES["attn_core"]
    assert all(core.search(call) for call in calls), calls
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)
    diffuse = harness.load_by_path("layer_metrics", "model.diffuse_ms").SCOPE
    names = re.findall(r'op_name="([^"]*)"', text)
    assert sum(1 for name in names if diffuse.search(name)) >= 4


#: sha256 of each accepted configuration's donating step at its cell's batch, lowered for
#: the described chip (StableHLO text, nothing compiled), as the parent of PR 39 lowers it
#: (mistral's and kimi's, which run no kernel of ``ops/attention.py``: PR 49 changed the
#: other four and left these two, which is how their cells are known not to have moved),
#: with each Mosaic kernel's serialized body left out: a body carries the source lines of
#: its callers in ``models/pattern.py``, which move with any edit above them, while
#: ``ops/attention.py`` and ``ops/index_scores.py`` themselves are the parent's files. A PR
#: that changes one of these steps on purpose records its own.
LOWERED_STEPS = {
    "mistral-7b-l2": "3fa3a52f6b2c48d9",
    "laguna-xs2-l5-ep8": "dd51e9a78133185b",  # PR 49: one backward kernel (9a71b4a41782a45a)
    "kimi-vl-a3b-l6-ep8": "21e86403b9123010",
    # PR 49: one backward kernel (ec74763a15e8bbb2 since PR 46: two more names a layer)
    "keye-vl2-30b-a3b-l6-ep8": "2ec17201366f9bc1",
    # with the file's optimizer where it states one (PR 48 recorded them from its parent)
    "solar-open2-250b-l4-ep40-tp8": "30d04eb85118b26c",  # PR 49: one backward kernel (8cd4db54ebcfc42c)
    "ouro-2.6b-l8": "e3cfee52ce528ad9",  # PR 49: one backward kernel (6d659a066881258b)
}


@pytest.mark.parametrize("name", list(LOWERED_STEPS))
def test_an_accepted_configurations_lowered_step_is_what_it_was(one_chip, as_on_a_tpu, name):
    """Heads held, the gate's form (or none), the head norms, a pattern without a rotary
    table, the delta kind and the objective are all read from the description: with
    next-token loss over the batch's own stream the six accepted configurations lower to
    the same program, byte for byte, so none of their cells can have moved."""
    import hashlib
    import re

    from benchmark import harness

    config = harness.read_json(harness.HERE, "configs", f"{name}.json")
    family = harness.load_family(config)
    cfg = family.program_config(config, config["batch"][1])
    stated = {"optimizer": config["optimizer"]} if "optimizer" in config else {}
    train_step, init_opt = family.make_train_step(cfg, **stated)
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(init_opt, params)
    on_chip = lambda tree: placed(tree, jax.tree.map(lambda _: one_chip, tree))  # noqa: E731
    text = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt), sds(tuple(config["batch"]), jnp.int32, one_chip)).as_text()
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED_STEPS[name]
