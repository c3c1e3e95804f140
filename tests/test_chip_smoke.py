"""``chip_smoke.py`` off the chip: its body at the smoke config on the
CPU, its refusal to run without a TPU, and the compile-cache rule its
entry point follows."""
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_body_at_smoke_config():
    cs = _chip_smoke()
    out = cs.run_modes(smoke=True, log=lambda *a: None)
    assert set(out["tokens"]) == {"collm", "standalone", "cloud"}
    # float32 on one backend: the partition reproduces the undivided model
    assert out["agreement"] == [1.0] * cs.N_PROMPTS
    assert out["logits"].shape[0] == cs.N_PROMPTS


def test_chip_smoke_refuses_without_tpu(capsys):
    cs = _chip_smoke()
    assert cs.main([]) != 0
    cap = capsys.readouterr()
    assert '"ok"' not in cap.out
    assert "no TPU" in cap.err


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_compile_cache_dir(tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR wins and entries land there; without it
    the cache is the fixed, gitignored .jax_cache/ at the repo root."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "d = enable_compile_cache()\n"
            "print(d)\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        code += "jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(3))\n"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.split()
    want = (str(tmp_path) if env_set
            else os.path.join(REPO, ".jax_cache"))
    assert lines[:2] == [want, want]
    if env_set:
        assert any(tmp_path.iterdir())
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
