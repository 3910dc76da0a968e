"""chip_smoke.py's phases at tiny sizes on the CPU (Pallas interpret mode),
its refusal of a non-TPU platform, and the launchers' no-silent-fallback
exits it relies on."""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _no_repo_cache(monkeypatch, tmp_path):
    """Entry points turn the persistent compile cache on in ``main()``.  Here
    the variable is set after jax read its settings, so ``enable()`` leaves
    this process's (disabled) cache alone and nothing is written into the
    checkout; the config is restored either way."""
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def clock(smoke):
    return smoke._CompileClock()


def test_main_refuses_non_tpu(smoke, capsys):
    """No accelerator: a non-zero exit and no result line."""
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_script_alone_fails(tmp_path):
    """Outside the repo (the script and nothing else) it cannot pass."""
    (tmp_path / "chip_smoke.py").write_text((ROOT / "chip_smoke.py").read_text())
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_phase_nyx_tiny(smoke, clock, capsys):
    smoke.phase_nyx(clock, n=128, sz_box=(8, 64, 128), fields=("baryon_density",),
                    backend="kernel", rates_bd=(4,))
    out = capsys.readouterr().out
    assert "[nyx] generate_s=" in out
    assert "words/emax/gtops == core zfp, bitwise" in out
    assert "stream == pack_codes(lorenzo3d_quantize_ref), bitwise" in out
    assert "decode == lorenzo3d_reconstruct_ref, bitwise" in out
    assert out.count("<= eb") == 2  # loosest and tightest bound
    assert "[nyx] compile_s=" in out and "peak_bytes=" in out


def test_phase_hacc_tiny(smoke, clock, capsys):
    smoke.phase_hacc(clock, grid=16, backend="kernel")
    out = capsys.readouterr().out
    assert out.count("1-D (4096): max|x^-x|") == 6
    assert out.count("rate=8: words/emax/gtops == core zfp, bitwise") == 6
    assert "[hacc] compile_s=" in out


def test_phase_serve_tiny_and_proof(smoke, clock, capsys):
    """The serve phase drains on the smoke config through the fused kvc
    kernel; off-chip the kernel proof must come back all False — the check
    the chip run asserts cannot pass in interpret mode."""
    eng = smoke.phase_serve(clock, smoke=True, n_requests=3, prompt_len=5,
                            max_new=4, slots=2, max_len=32, attention="fused")
    out = capsys.readouterr().out
    assert "3 requests x 4 new tokens completed, engine drained" in out
    assert "kvc_attention" in out and "vs kvc_decode_attention_ref" in out
    proof = smoke.kernel_proof(eng, sz_box=(8, 64, 128), zfp_n=8)
    assert set(proof) == {"sz_encode", "sz_decode", "zfp_encode", "zfp_decode",
                          "serve_decode_step"}
    assert not any(proof.values()), proof
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        smoke.phase_proof(clock, eng, sz_box=(8, 64, 128), zfp_n=8)


def test_phase_check_raises(smoke, clock):
    with pytest.raises(AssertionError, match="check failed: broken"):
        with smoke.Phase("x", clock) as ph:
            ph.check(False, "broken")


_INSITU4 = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util, sys
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    smoke.phase_insitu4(smoke._CompileClock(), n=128, box=(32, 64, 128), eb=30.0)
    print("INSITU4 OK")
"""


def test_phase_insitu4_virtual_devices(tmp_path):
    """The --chips 4 phase on four virtual CPU devices, in a subprocess
    (the parent's device count is pinned at its first jax use)."""
    script = tmp_path / "sub.py"
    script.write_text(textwrap.dedent(_INSITU4))
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script), str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "INSITU4 OK" in out
    assert out.count("distinct devices") == 4
    assert out.count("== single-device round trip on device 0, bitwise") == 2


# ------------------------------------------------- launcher exit codes ----


def test_serve_exits_nonzero_on_undrained(monkeypatch, capsys):
    from repro.launch import serve
    from repro.serving.engine import ServingEngine

    orig = ServingEngine.run_until_drained
    monkeypatch.setattr(ServingEngine, "run_until_drained",
                        lambda self, **kw: orig(self, max_ticks=1))
    rc = serve.main(["--arch", "starcoder2-3b", "--smoke", "--requests", "2",
                     "--max-new", "4", "--max-len", "32"])
    assert rc != 0
    assert "drain exhausted max_ticks" in capsys.readouterr().out


def test_serve_exits_nonzero_on_shed_without_drill(monkeypatch, capsys):
    """A replica that faults on every tick (e.g. a kernel that cannot
    compile) sheds every request: with no fault drill armed that must fail
    the run instead of exiting 0."""
    from repro.launch import serve
    from repro.serving.engine import ServingEngine

    def broken_tick(self):
        raise NotImplementedError("kernel lowering failed")

    monkeypatch.setattr(ServingEngine, "tick", broken_tick)
    rc = serve.main(["--arch", "starcoder2-3b", "--smoke", "--requests", "2",
                     "--max-new", "4", "--max-len", "32", "--replicas", "2"])
    assert rc != 0
    assert "shed" in capsys.readouterr().out


def test_serve_exits_zero_when_drained(capsys):
    from repro.launch import serve

    rc = serve.main(["--arch", "starcoder2-3b", "--smoke", "--requests", "2",
                     "--max-new", "4", "--max-len", "32"])
    assert rc == 0, capsys.readouterr().out


def _one_dev_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))


def test_insitu_hook_propagates_compile_error(tmp_path, monkeypatch):
    """A lowering error while compiling an eligible leaf is not a skip."""
    from repro.dist import insitu
    from repro.launch.train import build_insitu_hook

    def broken(*a, **k):
        raise NotImplementedError("Unimplemented primitive in Pallas TPU lowering")

    monkeypatch.setattr(insitu, "sharded_compress", broken)
    hook = build_insitu_hook(_one_dev_mesh(), str(tmp_path), eb=1e-3,
                             min_bytes=1024, arena=False, overlap=False)
    state = {"w": jnp.ones((64, 64), jnp.float32)}
    with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
        hook(1, state)


def test_insitu_hook_still_skips_ineligible(tmp_path, capsys):
    """The eligibility checks, run before compiling, still skip loudly."""
    from repro.launch.train import build_insitu_hook

    hook = build_insitu_hook(_one_dev_mesh(), str(tmp_path), eb=1e-3,
                             min_bytes=1024, arena=False, overlap=False)
    big = jnp.zeros(((1 << 26) + 64,), jnp.float32)
    hook(1, {"big": big, "ok": jnp.ones((64, 64), jnp.float32)})
    out = capsys.readouterr().out
    assert "skipping ['big']" in out and "int32 bit offsets" in out
    assert "1 fields" in out


# ------------------------------------------------------- compile cache ----


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    from repro import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))  # as jax reads it
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_repo(monkeypatch):
    from repro import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
