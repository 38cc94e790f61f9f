"""Fast checks of the benchmark's own arithmetic and its refusals."""
import json
import math
import os
import subprocess
import sys

import jax
import pytest

from bench.lib import cells, check, device, seeds

REPO = cells.REPO


def _mamba():
    cfg = cells.read_json(os.path.join(REPO, "bench/configs/mamba2-130m.json"))
    return cfg, cells.config_module(cfg)


def test_mamba2_flops_match_a_hand_count():
    cfg, mod = _mamba()
    # d=768, d_inner=1536, N=128, H=24, P=64, chunk Q=256 at seq 512:
    proj = 2 * 768 * (2 * 1536 + 2 * 128 + 24)          # 5,148,672
    conv = 2 * 4 * (1536 + 2 * 128)                     # 14,336
    intra = 2 * (256 * 128 + 256 * 24 * 64)             # 851,968
    inter = 2 * 2 * 128 * 24 * 64                       # 786,432
    out = 2 * 1536 * 768                                # 2,359,296
    logits = 2 * 768 * 50288                            # 50,277 ids padded to 16
    fwd = 24 * (proj + conv + intra + inter + out) + logits
    assert fwd == 297_099_264
    assert mod.flops_per_token(cfg, 512) == 3 * fwd == 891_297_792
    traffic = cells.read_json(os.path.join(
        REPO, "bench/traffic/4chip-gather-byrd-saga.json"))
    assert mod.flops_per_round(cfg, traffic) == 3 * fwd * 4 * 4 * 512


def test_mamba2_weights_have_the_published_parameter_count():
    cfg, mod = _mamba()
    shapes = jax.eval_shape(lambda k: mod.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))
    assert n == cfg["parameters"] == 128_965_056


def test_peaks_are_keyed_by_device_kind():
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("_source")


def test_seed_key_uses_every_bit():
    a = seeds.seed_key(5)
    b = seeds.seed_key(5 + 2 ** 32)
    assert not bool((a == b).all())
    with pytest.raises(ValueError):
        seeds.seed_key(-1)


def test_gaps_take_the_worst_leaf_against_the_median():
    ref = {"loss": [2.0], "grad": {"a": 1.0, "b": 2.0, "c": 0.0},
           "change": {"a": 1.0, "b": 2.0, "c": 5.0}}
    prog = {"loss": [2.002], "grad": {"a": 1.1, "b": 2.0, "c": 0.0},
            "change": {"a": 1.0, "b": 2.2, "c": 0.0}}
    g = check.gaps(prog, ref)
    assert g["loss_gap"] == pytest.approx(1e-3)
    assert g["grad_gap"] == pytest.approx(0.1)      # 0.1 / max(1, median 1)
    # "c" has no reference gradient: left out of the change.
    assert g["change_gap"] == pytest.approx(0.1)    # 0.2 / max(2, median 1.5)
    ok, out = check.judge(g, {"loss_gap": 1e-2, "grad_gap": 0.05})
    assert not ok and out["grad_gap"]["limit"] == 0.05


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"), "--workload",
         "logreg-ijcnn1.byrd-saga-signflip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_every_cell_finds_its_files():
    bench = cells.benchmark()
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], bench)
        assert cell["limits"]["limits"]
        cells.config_module(cell["config"])
        for m in cell["per_layer"]:
            assert callable(cells.metric_reader(m["name"]).read)
    json.dumps(bench)


def test_run_refuses_without_the_program(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("_out", ".jax_cache",
                                                  "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "logreg-ijcnn1.byrd-saga-signflip", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
