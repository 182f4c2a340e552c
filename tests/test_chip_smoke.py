"""``chip_smoke.py``: it refuses to run anywhere but on a TPU, and its
phases, rehearsed here on the CPU at the reduced config, pass."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke imported from the repo root, cut to the reduced config.
    The CPU reports no device memory, so peak bytes read as equal."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "SMOKE", True)
    monkeypatch.setattr(mod, "SEQ", 64)
    monkeypatch.setattr(mod, "STEPS", 8)
    monkeypatch.setattr(mod, "_peak_bytes", lambda: [1] * len(jax.devices()))
    return mod


def _run_script(path: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = {**os.environ, **env_extra}
    return subprocess.run([sys.executable, str(path)], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=path.parent)


def test_refuses_the_cpu():
    out = _run_script(ROOT / "chip_smoke.py", {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "TPU" in out.stderr


def test_refuses_to_run_without_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", alone)
    out = _run_script(alone, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout == ""


def test_one_chip_phases_rehearse_on_cpu(smoke, capsys):
    smoke.run(1)
    out = capsys.readouterr().out
    assert "train[lq_sgd]: losses" in out
    # every codec shape at both widths was compared
    shapes = smoke.codec_shapes(smoke._compressor("lq_sgd"))
    assert out.count("identical") == 2 * len(shapes)


def test_a_failed_check_fails_the_phase(smoke, monkeypatch):
    # a launcher whose wire metric disagrees with the static accounting
    from repro.launch import train
    real = train.run

    def lying_run(argv):
        return [dict(h, wire_mb_per_step=2 * h["wire_mb_per_step"])
                for h in real(argv)]

    monkeypatch.setattr(train, "run", lying_run)
    with pytest.raises(smoke.SmokeFailure, match="static accounting"):
        smoke.train_phase("lq_sgd", 1, smoke.CompileStats())


_FOUR_DEVICES = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [%(root)r, %(src)r]
    import jax
    import chip_smoke as cs
    cs.SMOKE, cs.SEQ, cs.STEPS = True, 64, 8
    cs._peak_bytes = lambda: [1] * len(jax.devices())
    cs.run(4)
    print("RESULT ok")
""")


def test_four_chip_phase_rehearses_on_cpu_devices():
    src = _FOUR_DEVICES % {"root": str(ROOT), "src": str(ROOT / "src")}
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "RESULT ok" in out.stdout
    assert "each in 4 shards on 4 distinct devices" in out.stdout
    wire = [l for l in out.stdout.splitlines() if l.startswith("wire:")]
    assert wire and float(wire[0].rsplit(" ", 1)[1]) < 0.01


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev  # nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_launcher_run_returns_history():
    from repro.launch import train

    history = train.run(["--arch", "mamba2-370m", "--smoke", "--steps", "3",
                         "--batch", "4", "--seq", "32", "--log-every", "1"])
    assert [h["step"] for h in history] == [0, 1, 2]
    assert all(h["wall_s"] >= 0 for h in history)
    assert json.dumps(history)  # plain floats, fit for a report
