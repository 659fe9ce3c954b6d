"""chip_smoke.py: it refuses to run off the chip, and its phases hold their
checks at smoke size on the CPU.

The refusal runs the script in a subprocess with ``JAX_PLATFORMS=cpu``: it
must exit non-zero and print no ``"ok": true`` line.  The phases run as
functions on smoke configs, so the token-identity and loss-agreement
checks the chip run relies on are exercised on every test run."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from conftest import subprocess_env

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ok_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("ok") is True:
            out.append(line)
    return out


@pytest.mark.parametrize("args,extra_env", [
    ([], {}),
    (["--four-chips"], {}),
    ([], {"REPRO_FORCE_INTERPRET": "0"}),
], ids=["one-chip", "four-chips", "force-interpret-set"])
def test_refuses_without_tpu(args, extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
    out = subprocess.run([sys.executable, str(SMOKE), *args], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0, out.stdout
    assert not _ok_lines(out.stdout), out.stdout


def test_serve_phase_checks_hold_at_smoke_size():
    from repro.configs import base
    smoke = _load_smoke()
    with smoke.CompileClock() as clock:
        smoke.serve_phase(base.get_smoke_config(smoke.SERVE_ARCH), seed=0,
                          clock=clock, min_prompt=8, max_prompt=96,
                          shared_prefix=48, new_tokens=6, chunk=32,
                          page=32)
    assert clock.seconds > 0


def test_train_phase_checks_hold_on_four_devices():
    code = textwrap.dedent(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(SMOKE)!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.configs import base
        assert len(jax.devices()) == 4
        with smoke.CompileClock() as clock:
            smoke.train_phase(base.get_smoke_config(smoke.TRAIN_ARCH),
                              jax.devices(), seed=0, clock=clock,
                              seq_len=32, batch=8)
        print("TRAIN PHASE OK")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         env=subprocess_env(4), capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TRAIN PHASE OK" in out.stdout


def test_compile_cache_dir_is_fixed(monkeypatch):
    from repro.launch import runtime
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    assert runtime.compile_cache_dir() == str(ROOT / ".jax_cache")
    monkeypatch.setenv(runtime.CACHE_ENV, "/some/where")
    assert runtime.compile_cache_dir() == "/some/where"
