"""CPU rehearsal of ``chip_smoke.py``: its phase functions at reduced sizes,
its refusal to report success without a TPU, and the compile-cache rule
of its entry points."""
import importlib.util
import os

import pytest

from mesh_harness import REPO, run_py


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_refuses_without_tpu(argv, capsys):
    assert _chip_smoke().main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out, out


def test_sim_phase_matches_its_reference():
    cs = _chip_smoke()
    out = cs.sim_phase(n=2000, steps=20)
    assert out["workers"] == [50, 20]
    assert out["rel_diff"] <= cs.SIM_RTOL
    assert out["param_rel_diff"] <= cs.SIM_PARAM_RTOL
    assert 0.0 < out["loss_device"] < 0.69  # learns from ln 2 at w = 0


def test_sim_phase_fails_a_run_one_step_short(monkeypatch):
    cs = _chip_smoke()
    run, calls = cs._sim_run, []

    def faulty(device, data, steps):
        calls.append(steps)  # the device run comes first, then the reference
        return run(device, data, steps - 1 if len(calls) == 1 else steps)

    monkeypatch.setattr(cs, "_sim_run", faulty)
    with pytest.raises(SystemExit, match="FAILED: sim"):
        cs.sim_phase(n=2000, steps=20)
    assert calls == [20, 20]


def test_train_phase_runs_main_in_process(tmp_path, monkeypatch):
    # An explicit cache directory keeps this test from writing one into
    # the checkout (enable_compile_cache defers to the variable).
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rows = _chip_smoke().train_phase(reduced=True, steps=2, seq=16)
    assert [r["step"] for r in rows] == [0, 1]


def test_kernel_check_on_a_four_device_mesh():
    out = run_py(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        from repro import compat
        err = chip_smoke.kernel_check(
            compat.make_mesh((4, 1), ("data", "model")), 4096)
        print("ERR", err)
    """, devices=4, timeout=120)
    assert "on devices [0,1,2,3]" in out
    assert "ERR" in out


def test_compile_cache_goes_where_the_variable_says(monkeypatch, tmp_path):
    import jax
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
