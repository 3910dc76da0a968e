"""A whole run of each cell at a small size on the CPU (the look for a chip
skipped): sound, it is correct; with the timed path broken underneath, or
with the bfloat16 reference in the program's place, it is not."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import cells, control, run
from repro.core import bitpack
from repro.core import zfp as core_zfp
from repro.core.api import CompressionResult


def small(name: str):
    """A cell of BENCHMARK.json at a small size."""
    cell = cells.load_cell(name)
    return dataclasses.replace(cell, config=dict(cell.config, grid=16 if "nyx" in name else 32))


def result(cell, wrap=None, seed=11):
    return run.run_cell(cell, seed, 0.2, False, time.perf_counter(), wrap=wrap)


class Broken:
    """A codec driver with one fault planted in its timed path."""

    def __init__(self, driver, fault):
        self.d, self.fault = driver, fault
        self.fetch_shape_depends_on_data = driver.fetch_shape_depends_on_data

    def compress(self, x, params):
        if self.fault == "half_left_out":
            flat = x.reshape(-1)
            x = flat.at[flat.size // 2:].set(0.0).reshape(x.shape)
        r = self.d.compress(x, params)
        if self.fault == "stream_altered":
            r = _flip_first_word(r)
        return r

    def fetch(self, r):
        return self.d.fetch(r)

    def upload(self, r, host):
        return self.d.upload(r, host)

    def decompress(self, r):
        x = self.d.decompress(r)
        if self.fault == "answer_altered":
            x = x.at[(0,) * x.ndim].add(1.0)
        return x


def _flip_first_word(r: CompressionResult) -> CompressionResult:
    def flip(words):
        return words.at[(0,) * words.ndim].set(words[(0,) * words.ndim] ^ jnp.uint32(1 << 30))

    payload = dict(r.payload)
    if payload.get("kernel"):
        p = payload["kpacked"]
        payload["kpacked"] = bitpack.PackedCodes(flip(p.words), p.widths, p.total_bits, p.n)
    else:
        parts = []
        for c in payload["parts"]:
            if isinstance(c, core_zfp.ZFPCompressed):
                parts.append(dataclasses.replace(c, words=flip(c.words)))
            else:
                p = c.packed
                parts.append(dataclasses.replace(
                    c, packed=bitpack.PackedCodes(flip(p.words), p.widths, p.total_bits, p.n)))
        payload["parts"] = parts
    jax.block_until_ready(payload)
    return CompressionResult(payload, r.nbytes, r.raw_nbytes, r.meta)


@pytest.mark.parametrize("name", ["nyx256.sz_tight", "nyx.zfp_r8", "hacc.sz_1d"])
def test_sound_run_is_correct(name):
    r = result(small(name))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 6
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert set(r["metrics"]) == {m["name"] for m in cells.load_cell("nyx.zfp_r8").end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("fault", ["stream_altered", "answer_altered", "half_left_out"])
@pytest.mark.parametrize("name", ["nyx256.sz_tight", "nyx.zfp_r8", "hacc.sz_1d"])
def test_fault_is_caught(name, fault):
    r = result(small(name), wrap=lambda d: Broken(d, fault))
    assert not r["correct"] and r["failed"] > 0


@pytest.mark.parametrize("name", ["nyx256.sz_tight", "nyx.zfp_r8", "hacc.sz_1d"])
def test_bf16_control_is_not_correct(name):
    cell = small(name)
    (line,) = control.readings(cell, [5], 0.0, program=False)
    assert not line["correct"]
    assert line["checks"]["stream_diff"]["value"] > 0
    if cell.mix["codec"] == "tpu-sz":
        assert line["checks"]["err_over_eb"]["value"] > 1.0
