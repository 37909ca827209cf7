"""A model family is files. Three tests on the CPU, run by hand with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- a third family (the dense block with tied embeddings) is added to a copy of
  ``benchmark/`` as new files only: its family file, its reference, a configuration and
  a manifest with one cell, and the copy's own ``rehearse.py`` drives that cell with no
  problem reported: what a ``model_config`` PR does, with no edit to a file that is there;
- a family that has no file ends in ``NoResult`` naming the file that was looked for;
- every configuration under ``benchmark/configs/`` names a family that exposes the whole
  contract, whose reference imports nothing of the program and has the program's leaves.
"""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_RESILIENCY_LOG_LEVEL", "WARNING")

from benchmark import harness  # noqa: E402

CONFIGS = sorted(glob.glob(os.path.join(harness.HERE, "configs", "*.json")))

TIED_FAMILY = '''
"""Family ``tied``: the dense block with the embedding as the head."""
from benchmark import harness

dense = harness.load_by_path("families", "dense")
REFERENCE = "tied"
TINY = dense.TINY
program_config = dense.program_config
param_specs = dense.param_specs


def _tied(params):
    return {**params, "lm_head": params["embed"].T}


def init_params(key, cfg):
    params = dense.init_params(key, cfg)
    del params["lm_head"]
    return params


def make_train_step(cfg):
    from tpu_resiliency.models import transformer

    return transformer.make_train_step_from_loss(
        lambda params, tokens: transformer.loss_fn(_tied(params), tokens, cfg))


def train_flops_per_token(config, seq):
    return dense.train_flops_per_token(config, seq)
'''

TIED_REFERENCE = '''
"""The dense reference with the embedding as the head."""
from benchmark.reference import model


def init_params(seed, cfg):
    params = model.init_params(seed, cfg)
    del params["lm_head"]
    return params


def loss(params, tokens, cfg, precision="f32"):
    return model.loss({**params, "lm_head": params["embed"].T}, tokens, cfg, precision)
'''


def test_a_third_family_is_new_files_only(tmp_path):
    shutil.copytree(harness.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = harness.read_json(ROOT, "BENCHMARK.json")
    config = {**harness.read_json(harness.HERE, "configs", "mistral-7b-l2.json"),
              "family": "tied", "tie_word_embeddings": True}
    cell = "tied_steady_noprof"
    manifest["configs"] = [{"name": "toy-tied", "file": "benchmark/configs/toy-tied.json"}]
    manifest["workloads"] = [{"name": cell, "config": "toy-tied",
                              "traffic": "steady_no_profiler", "chips": 1}]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:  # a listed metric is reported only in listed cells
            metric["workloads"] = [cell]
    new_files = {
        "benchmark/families/tied.py": TIED_FAMILY,
        "benchmark/reference/tied.py": TIED_REFERENCE,
        "benchmark/configs/toy-tied.json": json.dumps(config),
        "toy_manifest.json": json.dumps(manifest),
    }
    for name, text in new_files.items():
        assert not (tmp_path / name).exists(), name
        (tmp_path / name).write_text(text)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}  # tpu_resiliency only
    for trace in ("0", "1"):
        done = subprocess.run(
            [sys.executable, "benchmark/rehearse.py", "--manifest", "toy_manifest.json",
             "--workload", cell, "--seconds", "2", "--trace", trace],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        assert f"rehearsal of {cell}" in done.stderr and "problems []" in done.stderr
        assert '"number": "change_norms_worst_leaf"' in done.stdout


def test_an_unknown_family_names_the_file_it_looked_for(capsys):
    cell = harness.load_cell("mistral7b_steady")
    cell.config = {**cell.config, "family": "nonesuch"}
    run = harness.Run(cell, 11, 1.0, False, 0.0, rehearsal=True)
    try:
        with pytest.raises(harness.NoResult):
            harness.Session(run)
    finally:
        run.cleanup()
    assert os.path.join("benchmark", "families", "nonesuch.py") in capsys.readouterr().err


@pytest.mark.parametrize("path", CONFIGS, ids=[os.path.basename(p) for p in CONFIGS])
def test_a_configurations_family_exposes_the_contract(path):
    import jax

    config = harness.read_json(path)
    family = harness.load_family(config)  # raises where a name of the contract is missing
    assert family.train_flops_per_token(config, config["batch"][1]) > 0
    assert {"batch", "limits"} <= set(family.TINY)
    reference = harness.load_reference(config)
    with open(reference.__file__) as f:
        assert not re.search(r"^\s*(from|import)\s+tpu_resiliency", f.read(), re.M)
    tiny = {**config, **family.TINY}
    cfg = family.program_config(tiny, tiny["batch"][1])
    program = jax.eval_shape(lambda key: family.init_params(key, cfg), jax.random.PRNGKey(0))
    plain = jax.eval_shape(lambda: reference.init_params(0, tiny))
    leaves = lambda tree: {jax.tree_util.keystr(p): x  # noqa: E731
                           for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    shapes = lambda tree: {k: (x.shape, x.dtype) for k, x in leaves(tree).items()}  # noqa: E731
    assert shapes(program) == shapes(plain)
    assert leaves(family.param_specs(cfg)).keys() == leaves(program).keys()
    train_step, init_opt = family.make_train_step(cfg)
    assert train_step.__name__ == "train_step"  # the trace readers look for jit_train_step
    assert shapes(jax.eval_shape(init_opt, program)[0].mu) == shapes(program)
