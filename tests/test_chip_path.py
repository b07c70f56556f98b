"""The chip path's repairs, checked on the CPU: ``chip_smoke.py`` rehearsed at
a tiny size with its device gate steered from here, its refusals, the
compilation-cache helper, and ``serve.main``'s exit status."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.generator import ExtractiveLLM
from repro.launch import compile_cache, serve

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(cs, tmp_path):
    """Steer the script to the CPU at a tiny size (same phases, same code)."""
    cs.PLATFORM = "cpu"
    cs.RETRIEVE.update(n=2048, d=32, clusters=16, nq=6, nlist=8, nprobe=2,
                       pq_m=4)
    spec = json.loads(cs.SERVE_SPEC.read_text())
    spec["llm"]["options"].update(smoke=True, max_prompt=96, max_new=3)
    spec["gen"]["chunk_tokens"] = 32
    spec["embedder"]["options"]["dim"] = 32
    cs.SERVE_SPEC = tmp_path / "tiny_spec.json"
    cs.SERVE_SPEC.write_text(json.dumps(spec))
    cs.SERVE_REQUESTS = 4
    cs.OUT_DIR = tmp_path / "out"


def test_chip_smoke_runs_end_to_end_at_tiny_size(tmp_path, capsys,
                                                 monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    cs = _chip_smoke()
    _tiny(cs, tmp_path)
    assert cs.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert any("flat/none/fused" in ln for ln in lines)
    assert any("ivf/pq/fused" in ln for ln in lines)
    doc = json.loads((tmp_path / "out" / "chip_smoke_serve.json").read_text())
    assert doc["gen"]["tokens_out"] == 4 * 3        # warm-up not counted


def test_chip_smoke_refuses_a_cpu(capsys):
    cs = _chip_smoke()
    assert cs.main([]) == 1
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_chip_smoke_refuses_interpret_mode(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cs = _chip_smoke()
    cs.PLATFORM = "cpu"
    assert cs.main([]) == 1
    out, err = capsys.readouterr()
    assert "REPRO_KERNEL_MODE" in err and '"ok"' not in out


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


_FOUR_CHIPS_PROG = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, ".")
import chip_smoke as cs
cs.PLATFORM = "cpu"
cs.FOUR.update(n=4096, d=32, clusters=16, nq=6, requests=4)
sys.exit(cs.main(["--four-chips"]))
"""


def test_chip_smoke_four_chips_on_virtual_devices():
    """The four-chip phase on four virtual CPU devices: mesh path from the
    harness thread, a quarter of the rows per device, exact ids."""
    env = dict(os.environ)
    env.pop("REPRO_KERNEL_MODE", None)
    r = subprocess.run([sys.executable, "-c", _FOUR_CHIPS_PROG], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] and last["device"]["count"] == 4


# -- compilation cache -------------------------------------------------------


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_falls_back_to_the_repo_dir(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_leaves_the_env_dir_to_jax(monkeypatch, tmp_path,
                                                 restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # set none in code


_CACHE_PROG = r"""
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def test_compile_cache_is_written_to_the_env_dir(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run([sys.executable, "-c", _CACHE_PROG], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


# -- serve.main exit status ---------------------------------------------------

SERVE_ARGS = ["--config", "examples/specs/fused_retrieve.json", "--mode",
              "open", "--requests", "4", "--update-frac", "0", "--docs", "8"]


def test_serve_main_exits_zero_when_every_request_is_served(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    monkeypatch.chdir(ROOT)
    assert serve.main(SERVE_ARGS) == 0


def test_serve_main_exits_nonzero_when_a_stage_raises(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    monkeypatch.chdir(ROOT)
    calls = []
    real = ExtractiveLLM.generate

    def generate(self, prompts, contexts):
        calls.append(len(prompts))
        if len(calls) > 1:                 # the warm-up passes, requests fail
            raise RuntimeError("device error")
        return real(self, prompts, contexts)

    monkeypatch.setattr(ExtractiveLLM, "generate", generate)
    out = tmp_path / "run.json"
    assert serve.main(SERVE_ARGS + ["--json-out", str(out)]) == 1
    assert json.loads(out.read_text())["summary"]["n_failed"] == 4
