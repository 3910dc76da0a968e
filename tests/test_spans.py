"""Program spans on the profiler's clock and stable names for device work.

``obs.trace.span`` forwards to a recording JAX profiler as ``repro/<name>``
and stays the shared no-op when nothing records; the codec path's stage
scopes reach the compiled HLO's ``op_name`` metadata; every Pallas kernel
carries a stable ``name=`` from ``obs.trace.KERNEL_NAMES``.
"""

import ast
import glob
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitpack, sz
from repro.core.api import get_compressor
from repro.kernels import sz_fused
from repro.obs import trace as obs_trace

SRC = Path(__file__).resolve().parents[1] / "src"


def _host_event_names(log_dir) -> list[str]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines for e in line.events]


def test_span_reaches_profiler_trace_and_noop_returns(tmp_path):
    tr = obs_trace.Tracer()
    assert tr.span("idle") is obs_trace._NULL_SPAN
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("probe.off", k=1):  # tracer off, profiler on
            pass
        tr.enable()
        with tr.span("probe.on"):  # both on: Chrome JSON as before, and the profiler
            pass
        tr.disable()
    finally:
        jax.profiler.stop_trace()
    assert tr.span("idle") is obs_trace._NULL_SPAN
    assert [e["name"] for e in tr.events] == ["probe.on"]
    names = _host_event_names(tmp_path)
    assert "repro/probe.off" in names and "repro/probe.on" in names


def test_codec_spans_in_profiler_trace(tmp_path):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8, 8)), jnp.float32)
    comp = get_compressor("tpu-sz", backend="core")
    r = comp.compress(x, eb=0.1)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        r = comp.compress(x, eb=0.1)
        host = bitpack.to_storage(r.payload["parts"][0].packed)
        bitpack.from_storage(host["words"], host["widths"], int(host["n"]))
        comp.decompress(r)
    finally:
        jax.profiler.stop_trace()
    names = {n for n in _host_event_names(tmp_path) if n.startswith(obs_trace.PROFILER_PREFIX)}
    assert names == {f"repro/{n}" for n in ("api.compress", "api.decompress", "sync.total_bits",
                                            "sync.copy", "bitpack.to_storage",
                                            "bitpack.from_storage", "bitpack.extend")}


def test_obs_imports_no_jax():
    code = "import sys; import repro.obs; assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, env={"PYTHONPATH": str(SRC)})


def test_scope_names_are_listed():
    with pytest.raises(ValueError):
        obs_trace.scope("bitpack.unlisted")
    assert len(set(obs_trace.STAGE_SCOPES)) == len(obs_trace.STAGE_SCOPES)


def _op_names(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("stage, fn, args", [
    ("sz.predict", lambda x: sz.compress(x, 0.1), (jnp.ones((8, 8, 8)),)),
    ("bitpack.pack", lambda x: sz.compress(x, 0.1), (jnp.ones((8, 8, 8)),)),
    ("bitpack.unpack", lambda c: sz.decompress(c), (sz.compress(jnp.ones((8, 8, 8)), 0.1),)),
    ("sz.reconstruct", lambda c: sz.decompress(c), (sz.compress(jnp.ones((8, 8, 8)), 0.1),)),
    ("bitpack.compact", lambda r, c: bitpack.compact_streams(r, c, 40)[0],
     (jnp.ones((4, 10), jnp.uint32), jnp.array([3, 0, 10, 2]))),
    ("sz_fused.disassemble", lambda p: sz_fused._disassemble_stream(p)[0],
     (bitpack.PackedCodes(jnp.ones(130, jnp.uint32), jnp.ones(2, jnp.uint8), jnp.int32(0), 128),)),
])
def test_stage_scope_names_device_ops(stage, fn, args):
    """The scope heads the ``op_name`` path of ops its stage compiles to."""
    assert stage in obs_trace.STAGE_SCOPES
    assert f"/{stage}/" in _op_names(fn, *args)


def _pallas_calls():
    for path in sorted((SRC / "repro" / "kernels").glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) and getattr(node.func, "attr", None) == "pallas_call":
                yield path.name, {k.arg: k.value for k in node.keywords}


def test_every_pallas_call_has_a_listed_name():
    names = []
    for fname, kw in _pallas_calls():
        assert "name" in kw and isinstance(kw["name"], ast.Constant), fname
        names.append(kw["name"].value)
    assert sorted(names) == sorted(obs_trace.KERNEL_NAMES)
