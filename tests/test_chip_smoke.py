"""``chip_smoke.py`` rehearsed on the CPU at smoke size.

The phases run with the Pallas kernels in interpret mode; the replica path
runs on four virtual CPU devices in a child process.  The script itself
must refuse to run where JAX finds no TPU, and where it stands alone.
"""

import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.kernels.ops import use_impl  # noqa: E402
from repro.runtime.executors import ServeExecutor  # noqa: E402


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_serve_and_train_phases_at_smoke_size(capsys):
    dev = jax.devices()[0]
    serve_exec = ServeExecutor(max_batch=4, max_seq=320,
                               record_logits=chip_smoke.RECORD)
    with use_impl("interpret"):
        sys_ = chip_smoke.build_system(["qwen3-smoke", "qwen2-smoke"], [dev],
                                       serve_exec)
        chip_smoke.serve_phase(sys_, serve_exec, "qwen3-smoke", dev)
        chip_smoke.train_phase(sys_, "qwen2-smoke", batch=2, seq=64)
    out = capsys.readouterr().out
    assert out.count("serve job ") == len(chip_smoke.SERVE_JOBS)
    assert "pallas vs reference" in out and "train job " in out


def test_logit_gate_fails_beyond_tolerance():
    import numpy as np
    want = [np.ones((2, 8), np.float32)]
    chip_smoke.compare_logits([want[0] + 0.04], want, "within")
    with pytest.raises(chip_smoke.SmokeFailure, match="differ"):
        chip_smoke.compare_logits([want[0] + 0.06], want, "beyond")


def test_replica_phase_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import jax
        import chip_smoke
        from repro.kernels.ops import use_impl
        with use_impl("interpret"):
            chip_smoke.replica_phase("qwen3-smoke", jax.devices()[:4],
                                     max_seq=256)
        print("REPLICA_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "clusters that served" in out.stdout
    assert "REPLICA_OK" in out.stdout


@pytest.mark.parametrize("alone", [False, True], ids=["cpu", "alone"])
def test_script_refuses_without_tpu(tmp_path, alone):
    """No TPU, or no repo beside the script: non-zero, no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    env = _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
        env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=120, cwd=tmp_path, env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if not alone:
        assert "no TPU found" in out.stderr


def test_compile_cache_directory(tmp_path):
    """The env var's directory gets the cache files; without it the cache
    is the fixed, git-ignored path in the checkout."""
    code = textwrap.dedent("""
        import os, sys
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache as cc
        want = sys.argv[1]
        if want == "default":
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
            assert cc.configure_compile_cache() == cc.DEFAULT_CACHE_DIR
        else:
            assert cc.configure_compile_cache() == want
            jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
            assert os.listdir(want), "no cache entry written"
        print("CACHE_OK")
    """)
    d = str(tmp_path)
    for arg in (d, "default"):
        out = subprocess.run(
            [sys.executable, "-c", code, arg], capture_output=True,
            text=True, timeout=120, cwd=ROOT,
            env=_env(JAX_COMPILATION_CACHE_DIR=d))
        assert "CACHE_OK" in out.stdout, out.stderr[-2000:]
    from repro.launch.compile_cache import DEFAULT_CACHE_DIR
    assert os.path.dirname(DEFAULT_CACHE_DIR) == ROOT
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = f.read().split()
    assert os.path.basename(DEFAULT_CACHE_DIR) + "/" in ignored
