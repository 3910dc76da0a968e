"""Arena-batched snapshot compression (`repro.core.arena` + the
`dist.insitu` bucket path).

The load-bearing property is **byte-identity**: each leaf's slice of a
bucket arena must equal the stream the per-leaf path produces today
(``sz.compress`` on the flat leaf; ``insitu.sharded_compress`` per shard),
so batching whole pytrees into O(#buckets) launches changes *nothing* about
the bits on disk.  Covered here: the shared compaction primitives, the
batched row packer, bucket planning, the hypothesis cross-path property
(with a deterministic fallback sweep, house style), the batched fused
Pallas kernels, the fixed-rate ZFP arena, and the checkpoint-manager arena
format (one ``arena_iNNNNN_sNNN.bin`` per shard + descriptor index,
legacy per-leaf format still restorable).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as PS

from repro.core import arena, bitpack
from repro.core import sz as sz_core
from repro.core import zfp as zfp_core

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401


# ------------------------------------------------------------ primitives ---


class TestCompaction:
    def test_exclusive_cumsum(self):
        x = jnp.asarray([3, 0, 5, 1], jnp.int32)
        np.testing.assert_array_equal(np.asarray(bitpack.exclusive_cumsum(x)),
                                      [0, 3, 3, 8])

    def test_compact_streams_matches_naive_concat(self):
        rng = np.random.default_rng(0)
        rows = rng.integers(0, 2**32, size=(7, 11), dtype=np.uint32)
        counts = rng.integers(0, 12, size=7).astype(np.int32)
        cap = int(counts.sum()) + 5
        words, offsets, used = bitpack.compact_streams(
            jnp.asarray(rows), jnp.asarray(counts), cap)
        ref = np.concatenate([rows[r, : counts[r]] for r in range(7)])
        assert int(used) == len(ref)
        np.testing.assert_array_equal(np.asarray(words)[: len(ref)], ref)
        assert (np.asarray(words)[len(ref):] == 0).all()
        np.testing.assert_array_equal(np.asarray(offsets),
                                      np.cumsum(counts) - counts)

    def test_compact_streams_zero_count_rows(self):
        rows = jnp.zeros((3, 4), jnp.uint32).at[1, :2].set(jnp.uint32(9))
        words, offsets, used = bitpack.compact_streams(
            rows, jnp.asarray([0, 2, 0]), 6)
        np.testing.assert_array_equal(np.asarray(words), [9, 9, 0, 0, 0, 0])
        assert int(used) == 2

    @staticmethod
    def _case(name):
        """(rows, counts, capacity) of one named compaction case."""
        rng = np.random.default_rng(sum(map(ord, name)))
        n_rows, width, garbage = 300, 64, False
        if name == "single_row":
            n_rows = 1
        elif name == "arena_width":
            n_rows, width = 37, 66  # core.arena rows are P + 2 words wide
        elif name.startswith("capacity"):
            n_rows = 40
        elif name == "garbage_past_count":
            garbage = True
        counts = 2 * rng.integers(0, width // 2 + 1, size=n_rows)
        if name == "w64_even_zero_runs":
            counts[10:60] = 0
            counts[rng.random(n_rows) < 0.3] = 0
            counts[-20:] = 0
        elif name == "top_shift_bit":
            # every row but the last is empty: the last moves left by
            # (R - 1) * W = 2**14, the top bit of the pass range
            n_rows = 257
            counts = np.zeros(n_rows, np.int64)
            counts[-1] = width
        rows = rng.integers(1, 2**32, size=(n_rows, width), dtype=np.uint32)
        if not garbage:
            rows[np.arange(width)[None, :] >= counts[:, None]] = 0
        used = int(counts.sum())
        capacity = {"capacity_below_rw": n_rows * width - 3,
                    "capacity_equal_rw": n_rows * width,
                    "capacity_above_rw": n_rows * width + 9}.get(name, used + 2)
        return rows, counts.astype(np.int32), capacity

    @pytest.mark.parametrize("name", [
        "w64_even_zero_runs", "garbage_past_count", "capacity_below_rw",
        "capacity_equal_rw", "capacity_above_rw", "single_row", "top_shift_bit",
        "arena_width"])
    def test_compact_streams_property(self, name):
        rows, counts, capacity = self._case(name)
        words, offsets, used = jax.jit(bitpack.compact_streams, static_argnums=2)(
            jnp.asarray(rows), jnp.asarray(counts), capacity)
        ref = np.concatenate([rows[r, : counts[r]] for r in range(len(counts))])
        want = np.zeros(capacity, np.uint32)
        want[: min(len(ref), capacity)] = ref[:capacity]
        assert words.shape == (capacity,)
        np.testing.assert_array_equal(np.asarray(words), want)
        np.testing.assert_array_equal(np.asarray(offsets), np.cumsum(counts) - counts)
        assert int(used) == len(ref)

    def test_compact_streams_has_no_gather_or_search(self):
        jaxpr = jax.make_jaxpr(lambda r, c: bitpack.compact_streams(r, c, 600))(
            jnp.zeros((9, 64), jnp.uint32), jnp.zeros((9,), jnp.int32))
        found = re.findall(r"\b(gather|while|sort|scatter[-\w]*)\b", str(jaxpr))
        assert not found, found

    def test_fused_assembler_uses_shared_compaction(self):
        # the dedup: sz_fused._assemble_stream must be byte-identical to
        # pack_codes on the same codes (the embedded-reference pin for the
        # fused path lives in test_kernels; this pins the refactor)
        from repro.kernels import sz_fused as szf

        rng = np.random.default_rng(1)
        codes = rng.integers(-(2**10), 2**10, size=(1024, 64)).astype(np.int32)
        u = bitpack.zigzag(jnp.asarray(codes.reshape(-1))).reshape(1024, 64)
        width = jnp.max(bitpack.bitlength(u), axis=1)
        block_words = szf._pack_blocks(u, width)
        packed = szf._assemble_stream(block_words, width, codes.size)
        ref = bitpack.pack_codes(jnp.asarray(codes.reshape(-1)))
        np.testing.assert_array_equal(np.asarray(packed.words), np.asarray(ref.words))
        assert int(packed.total_bits) == int(ref.total_bits)


class TestRowPacker:
    @pytest.mark.parametrize("ns", [(256,), (100, 64, 1, 200, 3), (64, 64)])
    def test_byte_identity_vs_per_leaf(self, ns):
        rng = np.random.default_rng(sum(ns))
        P = max(arena.row_length(n) for n in ns)
        codes = np.zeros((len(ns), P), np.int32)
        for b, n in enumerate(ns):
            codes[b, :n] = rng.integers(-(2**20), 2**20, size=n)
        rows, counts, widths, tb = bitpack.pack_codes_rows(
            jnp.asarray(codes), jnp.asarray(ns))
        for b, n in enumerate(ns):
            ref = bitpack.pack_codes(jnp.asarray(codes[b, :n]))
            store = bitpack.to_storage(ref)
            assert int(counts[b]) == len(store["words"])
            np.testing.assert_array_equal(
                np.asarray(rows)[b, : int(counts[b])], store["words"])
            nb = -(-n // bitpack.BLOCK)
            np.testing.assert_array_equal(np.asarray(widths)[b, :nb],
                                          store["widths"])
            assert (np.asarray(widths)[b, nb:] == 0).all()
            assert int(tb[b]) == int(ref.total_bits)
        back = np.asarray(bitpack.unpack_codes_rows(rows, widths))
        np.testing.assert_array_equal(back, codes)

    def test_extreme_codes(self):
        codes = np.zeros((2, 64), np.int32)
        codes[0, :7] = [0, 1, -1, 2**30, -(2**30), 2**31 - 1, -(2**31)]
        rows, counts, widths, _ = bitpack.pack_codes_rows(
            jnp.asarray(codes), jnp.asarray([7, 64]))
        back = np.asarray(bitpack.unpack_codes_rows(rows, widths))
        np.testing.assert_array_equal(back, codes)


# -------------------------------------------------------------- planning ---


class TestPlanning:
    def test_row_length_pow2_blocks(self):
        assert arena.row_length(1) == 64
        assert arena.row_length(64) == 64
        assert arena.row_length(65) == 128
        assert arena.row_length(129) == 256
        assert arena.row_length(64 * 64) == 64 * 64
        assert arena.row_length(64 * 64 + 1) == 64 * 128

    def test_buckets_are_o_log_not_o_leaves(self):
        # 200 leaves, sizes spread over a 2^10 range -> <= ~11 buckets
        entries = [(f"l{i}", (37 + (i * 97) % 60000,), "float32")
                   for i in range(200)]
        plan = arena.plan_buckets(entries)
        assert len(plan) <= 12, [b.padded for b in plan]
        assert sum(b.rows for b in plan) == 200

    def test_budget_splits_buckets(self):
        entries = [(f"l{i}", (1024,), "float32") for i in range(8)]
        plan = arena.plan_buckets(entries, elem_budget=3 * 1024)
        assert all(b.rows <= 3 for b in plan)
        assert sum(b.rows for b in plan) == 8

    def test_plan_deterministic(self):
        entries = [("b", (100,), "float32"), ("a", (90,), "float32"),
                   ("c", (5000,), "bfloat16")]
        p1, p2 = arena.plan_buckets(entries), arena.plan_buckets(entries)
        assert p1 == p2
        assert p1[0].names == ("b", "a")  # insertion order inside a bucket


# ------------------------------------------- cross-path property (core) ----


def _assert_bucket_matches_per_leaf(named, eb):
    """The acceptance property: compress a pytree's leaves through the
    arena; every leaf's stream slice must be byte-identical to the per-leaf
    coder on the flat leaf, the batched decode bitwise equal to the
    per-leaf decode, and the host restore equal to both."""
    plan = arena.plan_buckets([(k, v.shape, v.dtype) for k, v in named])
    by_key = dict(named)
    for b in plan:
        leaves = [jnp.asarray(by_key[nm]) for nm in b.names]
        a = arena.sz_compress_bucket(leaves, b, eb)
        h = arena.to_host(a, b)
        dec = arena.sz_decompress_bucket(a, b)
        back = arena.host_restore(
            arena.host_meta(h), [arena.payload_encode(s) for s in h.shards])
        for i, nm in enumerate(b.names):
            flat = jnp.asarray(by_key[nm]).astype(jnp.float32).reshape(-1)
            ref = sz_core.compress(flat, eb)
            store = bitpack.to_storage(ref.packed)
            ls = arena.leaf_stream(h, i)
            np.testing.assert_array_equal(ls["words"], store["words"])
            np.testing.assert_array_equal(ls["widths"], store["widths"])
            assert ls["total_bits"] == int(ref.packed.total_bits)
            assert float(np.asarray(a.eb_i)[i]) == float(np.asarray(ref.eb))
            ref_x = np.asarray(sz_core.decompress(ref))
            got = np.asarray(dec[i], np.float32).reshape(-1)
            exp = np.asarray(
                jnp.asarray(ref_x).reshape(b.shapes[i]).astype(b.dtypes[i]),
                np.float32).reshape(-1)
            np.testing.assert_array_equal(got, exp)
            np.testing.assert_array_equal(
                back[nm].astype(np.float32).reshape(-1), got)
            assert back[nm].dtype == np.dtype(b.dtypes[i])
        # accounting: stored = live arena words + the descriptor sidecars
        words_b = int(np.sum(np.asarray(a.counts))) * 4
        sidecar_b = sum(int(np.asarray(h.shards[0][k]).nbytes)
                        for k in ("widths", "offsets", "counts", "total_bits"))
        assert h.nbytes_stored() == words_b + sidecar_b


def _random_tree(seed):
    rng = np.random.default_rng(seed)
    n_leaves = int(rng.integers(1, 7))
    named = []
    for i in range(n_leaves):
        rank = int(rng.integers(1, 4))
        shape = tuple(int(rng.integers(1, 14)) for _ in range(rank))
        dtype = [np.float32, "bfloat16"][int(rng.integers(0, 2))]
        x = (rng.normal(size=shape) * 10.0 ** int(rng.integers(-1, 3))).astype(np.float32)
        named.append((f"leaf{i}", jnp.asarray(x).astype(dtype)))
    eb = float(10.0 ** rng.integers(-4, 0))
    return named, eb


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_arena_matches_per_leaf_property(seed):
        """Random leaf-count/shape/dtype pytrees: the arena path is
        byte-identical per leaf to the per-leaf path."""
        named, eb = _random_tree(seed)
        _assert_bucket_matches_per_leaf(named, eb)

else:  # deterministic guard, house style

    @pytest.mark.parametrize("seed", range(6))
    def test_arena_matches_per_leaf_property(seed):
        named, eb = _random_tree(seed)
        _assert_bucket_matches_per_leaf(named, eb)


def test_arena_zero_and_constant_leaves():
    # degenerate widths: all-zero and constant leaves must round-trip
    named = [("z", jnp.zeros((64,), jnp.float32)),
             ("c", jnp.full((100,), 3.25, jnp.float32))]
    _assert_bucket_matches_per_leaf(named, 1e-2)


def test_host_restore_rejects_sparse_payloads():
    named = [("w", jnp.asarray(np.random.default_rng(0)
                               .normal(size=(32, 8)).astype(np.float32)))]
    b = arena.plan_buckets([(k, v.shape, v.dtype) for k, v in named])[0]
    a = arena.sz_compress_bucket([named[0][1]], b, 1e-3)
    h = arena.to_host(a, b)
    meta = arena.host_meta(h)
    meta["arena"]["grid"] = 2  # claims 2 shards, 1 payload present
    with pytest.raises(IOError, match="payload"):
        arena.host_restore(meta, [arena.payload_encode(h.shards[0])])


# --------------------------------------------------- fused batched kernel --


class TestFusedBatched:
    def test_batched_kernel_byte_identical_per_row(self):
        from repro.kernels import sz_fused as szf

        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(2, 8, 64, 128)).astype(np.float32) * 20)
        eb = jnp.asarray([0.5, 0.05], jnp.float32)
        ar, widths, offs, counts, tb, used = szf.fused_compress_batched(x, eb)
        pos = 0
        for b in range(2):
            ref = szf.fused_compress(x[b], eb[b])
            store = bitpack.to_storage(ref)
            assert int(offs[b]) == pos
            assert int(counts[b]) == len(store["words"])
            np.testing.assert_array_equal(
                np.asarray(ar)[pos : pos + int(counts[b])], store["words"])
            np.testing.assert_array_equal(np.asarray(widths)[b], store["widths"])
            assert int(tb[b]) == int(ref.total_bits)
            pos += int(counts[b])
        assert int(used) == pos
        y = szf.fused_decompress_batched(ar, widths, (8, 64, 128), eb)
        for b in range(2):
            ref = szf.fused_decompress(szf.fused_compress(x[b], eb[b]),
                                       (8, 64, 128), eb[b])
            np.testing.assert_array_equal(np.asarray(y[b]), np.asarray(ref))

    @staticmethod
    def _mixed_field():
        """A (64, 64, 128) field whose tiles span block widths 0 to 32:
        zeros, a smooth wave, a random walk, noise, and noise whose
        amplitude doubles every two y rows (at an internal bound of 0.5)."""
        rng = np.random.default_rng(15)
        x = np.zeros((64, 64, 128), np.float32)
        z, y, w = np.ogrid[:8, :64, :128]
        x[8:16] = np.sin(0.05 * (z + y + w)) * 3
        x[16:32] = np.cumsum(rng.normal(size=(16, 64, 128)), axis=2) * 20
        x[32:48] = rng.normal(size=(16, 64, 128)) * 1e4
        x[48:64] = rng.normal(size=(16, 64, 128)) * 2.0 ** (np.arange(64) / 2)[:, None]
        return jnp.asarray(x), jnp.float32(0.5)

    def test_fused_stream_byte_identical_across_widths(self):
        from repro.kernels import sz_fused as szf
        from repro.kernels.lorenzo3d import lorenzo3d_quantize

        x, eb = self._mixed_field()
        got = szf.fused_compress(x, eb, interpret=True)
        ref = bitpack.pack_codes(szf.tile_major_flatten(lorenzo3d_quantize(x, eb)))
        widths = np.asarray(got.widths)
        assert widths.min() == 0 and widths.max() == 32
        np.testing.assert_array_equal(widths, np.asarray(ref.widths))
        np.testing.assert_array_equal(np.asarray(got.words), np.asarray(ref.words))
        assert int(got.total_bits) == int(ref.total_bits)

    def test_batched_stream_byte_identical_across_widths(self):
        from repro.kernels import sz_fused as szf

        x, eb = self._mixed_field()
        rows = x.reshape(4, 16, 64, 128)  # each row holds two kinds of tile
        ebs = jnp.full((4,), eb)
        ar, widths, offs, counts, tb, used = szf.fused_compress_batched(
            rows, ebs, interpret=True)
        pos = 0
        for b in range(4):
            store = bitpack.to_storage(szf.fused_compress(rows[b], eb, interpret=True))
            assert int(offs[b]) == pos and int(counts[b]) == len(store["words"])
            np.testing.assert_array_equal(
                np.asarray(ar)[pos : pos + int(counts[b])], store["words"])
            np.testing.assert_array_equal(np.asarray(widths)[b], store["widths"])
            pos += int(counts[b])
        assert int(used) == pos
        assert (np.asarray(ar)[pos:] == 0).all()


# --------------------------------------------------------------- ZFP arena --


class TestZfpArena:
    def test_leaf_slices_byte_identical(self):
        rng = np.random.default_rng(3)
        leaves = [jnp.asarray(rng.normal(size=s).astype(np.float32))
                  for s in [(8, 8, 8), (12, 8, 4), (6, 5, 9)]]
        a = arena.zfp_compress_bucket(leaves, 8)
        assert a.ranges == (0,) + tuple(np.cumsum(
            [zfp_core.n_blocks_for(x.shape) for x in leaves]))
        for i, x in enumerate(leaves):
            ref = zfp_core.compress(x, 8)
            v = arena.zfp_leaf_view(a, i, x.shape)
            np.testing.assert_array_equal(np.asarray(v.words), np.asarray(ref.words))
            np.testing.assert_array_equal(np.asarray(v.emax), np.asarray(ref.emax))
            np.testing.assert_array_equal(np.asarray(v.gtops), np.asarray(ref.gtops))
        dec = arena.zfp_decompress_bucket(a, [x.shape for x in leaves])
        for i, x in enumerate(leaves):
            np.testing.assert_array_equal(
                np.asarray(dec[i]),
                np.asarray(zfp_core.decompress(zfp_core.compress(x, 8))))

    def test_fused_arena_wrappers_match_blocks(self):
        from repro.kernels import zfp_fused as zf

        rng = np.random.default_rng(4)
        blocks = jnp.asarray(rng.normal(size=(zf.BLOCKS_PER_TILE, 4, 4, 4))
                             .astype(np.float32))
        flat, emax, gtops = zf.fused_compress_arena(blocks, 6)
        words, emax2, gtops2 = zf.fused_compress_blocks(blocks, 6)
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(words).reshape(-1))
        back = zf.fused_decompress_arena(flat, emax, gtops, 6)
        np.testing.assert_array_equal(
            np.asarray(back),
            np.asarray(zf.fused_decompress_blocks(words, emax2, gtops2, 6)))


# --------------------------------------------------- sharded bucket path ---


def _subset_mesh(n):
    devs = jax.devices()
    if n > len(devs):
        pytest.skip(f"needs {n} devices, have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n]).reshape(n), ("data",))


class TestShardedArena:
    def test_plan_rejects_non_leading_partitions(self):
        from repro.dist import insitu

        mesh = jax.sharding.AbstractMesh((2,), ("data",))
        entries = [("ok", (8, 4), np.float32, PS("data")),
                   ("bad", (8, 4), np.float32, PS(None, "data")),
                   ("odd", (7,), np.float32, PS("data"))]
        buckets, skipped = insitu.plan_arena(entries, mesh)
        assert [b.names for b in buckets] == [("ok",)]
        assert sorted(k for k, _ in skipped) == ["bad", "odd"]

    @pytest.mark.parametrize("n_dev", [1, 2])
    def test_sharded_bucket_matches_per_leaf_and_single_device(self, n_dev):
        """Per-shard byte-identity with the per-leaf sharded path AND
        bitwise round-trip equality with the single-device flat path (sized
        to the available devices; real under the CI dist step)."""
        from jax.sharding import NamedSharding

        from repro.dist import insitu

        mesh = _subset_mesh(n_dev)
        rng = np.random.default_rng(n_dev)
        leaves = {"w1": rng.normal(size=(16, 24)).astype(np.float32) * 4,
                  "w2": rng.normal(size=(16, 24)).astype(np.float32),
                  "b": rng.normal(size=(64,)).astype(np.float32)}
        spec = PS("data")
        sharded = {k: jax.device_put(jnp.asarray(v), NamedSharding(mesh, spec))
                   for k, v in leaves.items()}
        entries = [(k, v.shape, v.dtype, spec) for k, v in leaves.items()]
        buckets, skipped = insitu.plan_arena(entries, mesh)
        assert not skipped
        EB = 1e-2
        for b in buckets:
            stream = insitu.sharded_compress_arena(
                [sharded[nm] for nm in b.names], b, mesh, EB)
            h = insitu.arena_to_host(stream)
            for i, nm in enumerate(b.names):
                flat = jnp.asarray(leaves[nm]).reshape(-1)
                spec1 = PS("data") if b.axis else PS()
                ref = insitu.to_host(insitu.sharded_compress(
                    jax.device_put(flat, NamedSharding(mesh, spec1)),
                    "sz", mesh, spec1, eb=EB))
                for s in range(b.grid):
                    ls = arena.leaf_stream(h, i, s)
                    np.testing.assert_array_equal(ls["words"],
                                                  ref.shards[s][1]["words"])
                    np.testing.assert_array_equal(ls["widths"],
                                                  ref.shards[s][1]["widths"])
            dec = insitu.sharded_decompress_arena(stream, mesh)
            back = arena.host_restore(
                arena.host_meta(h), [arena.payload_encode(s) for s in h.shards])
            for i, nm in enumerate(b.names):
                flat = jnp.asarray(leaves[nm]).reshape(-1)
                ref = np.asarray(sz_core.decompress(sz_core.compress(flat, EB)))
                np.testing.assert_array_equal(np.asarray(dec[i]).reshape(-1), ref)
                np.testing.assert_array_equal(back[nm], np.asarray(dec[i]))


# ----------------------------------------------------- checkpoint format ---


class TestManagerArenaFormat:
    def _snapshot(self, tmp_path, rng):
        from repro.checkpoint.manager import CheckpointManager

        tree = {"w": rng.normal(size=(48, 32)).astype(np.float32),
                "b": rng.normal(size=(96,)).astype(np.float32)}
        plan = arena.plan_for_tree(tree)
        state = {}
        for k, b in enumerate(plan):
            a = arena.sz_compress_bucket(
                [jnp.asarray(tree[nm.strip("['']")]) for nm in b.names], b, 1e-3)
            state[f"arena{k:03d}"] = arena.to_host(a, b)
        mgr = CheckpointManager(tmp_path, async_save=False)
        mgr.save(1, state)
        return tree, plan, state, mgr

    def test_one_file_per_bucket_and_restore(self, tmp_path):
        tree, plan, state, mgr = self._snapshot(tmp_path, np.random.default_rng(5))
        d = sorted(tmp_path.glob("step_*"))[0]
        files = sorted(p.name for p in d.glob("*.bin"))
        assert files == [f"arena_{i:05d}_s000.bin" for i in range(len(plan))]
        out, _ = mgr.restore(state_like={k: 0 for k in state})
        for k, b in enumerate(plan):
            got = out[f"arena{k:03d}"]
            for nm in b.names:
                ref = tree[nm.strip("['']")]
                assert np.abs(got[nm] - ref).max() <= 1e-3 * (1 + 1e-5)

    def test_corruption_detected(self, tmp_path):
        _, _, state, mgr = self._snapshot(tmp_path, np.random.default_rng(6))
        d = sorted(tmp_path.glob("step_*"))[0]
        blob = sorted(d.glob("arena_*.bin"))[0]
        raw = bytearray(blob.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        blob.write_bytes(bytes(raw))
        with pytest.raises(IOError):
            mgr.restore(state_like={k: 0 for k in state})


# --------------------------------------------------- kernel-route buckets --


class TestKernelBuckets:
    """3-D TILE-aligned replicated leaves route through the fused tile
    kernel (codec ``arena-szk``) instead of the flat per-row Lorenzo."""

    def test_plan_kernel_buckets_eligibility(self):
        from repro.dist import insitu

        mesh = jax.sharding.AbstractMesh((2,), ("data",))
        entries = [
            ("tile_a", (8, 64, 128), np.float32, PS()),     # kernel route
            ("tile_b", (8, 64, 128), np.float32, PS()),     # same bucket
            ("misaligned", (8, 64, 127), np.float32, PS()),  # flat route
            ("flat2d", (64, 64), np.float32, PS()),          # flat route
            ("sharded", (8, 64, 128), np.float32, PS("data")),  # flat route
        ]
        kbuckets, rest = insitu.plan_kernel_buckets(entries, mesh)
        assert len(kbuckets) == 1
        assert kbuckets[0].names == ("tile_a", "tile_b")
        assert kbuckets[0].padded == 8 * 64 * 128  # tile rows carry no pad
        assert [e[0] for e in rest] == ["misaligned", "flat2d", "sharded"]

    def test_szk_byte_identity_vs_tile_kernel(self):
        from repro.kernels import ops as kops

        rng = np.random.default_rng(7)
        eb = 1e-3
        leaves = [jnp.asarray((rng.normal(size=(8, 64, 128)) * (i + 1))
                              .astype(np.float32)) for i in range(3)]
        n = 8 * 64 * 128
        b = arena.Bucket(n, ("x0", "x1", "x2"),
                         ((8, 64, 128),) * 3, ("float32",) * 3, (n,) * 3)
        a = arena.szk_compress_bucket(leaves, b, eb)
        h = arena.to_host(a, b, codec=arena.CODEC_SZK)
        assert h.codec == arena.CODEC_SZK
        sh = h.shards[0]
        dec = arena.szk_decompress_bucket(a, b)
        for i, x in enumerate(leaves):
            # the arena row must be bit-for-bit the standalone tile coder
            packed, pshape, eb_i = kops.sz_compress_kernel(x, eb, path="xla")
            ref = bitpack.to_storage(packed)
            off, cnt = int(sh["offsets"][i]), int(sh["counts"][i])
            assert cnt == len(ref["words"])
            np.testing.assert_array_equal(sh["arena"][off:off + cnt],
                                          ref["words"])
            np.testing.assert_array_equal(sh["widths"][i], ref["widths"])
            # device-side batched decode matches too
            np.testing.assert_array_equal(
                np.asarray(dec[i]),
                np.asarray(kops.sz_decompress_kernel(
                    packed, pshape, (8, 64, 128), eb_i, path="xla")))

    def test_szk_manager_roundtrip(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager

        rng = np.random.default_rng(8)
        eb = 1e-3
        raw = {f"f{i}": (rng.normal(size=(8, 64, 128)) * 5).astype(np.float32)
               for i in range(2)}
        n = 8 * 64 * 128
        b = arena.Bucket(n, tuple(raw), ((8, 64, 128),) * 2,
                         ("float32",) * 2, (n,) * 2)
        a = arena.szk_compress_bucket([jnp.asarray(v) for v in raw.values()],
                                      b, eb)
        state = {"karena000": arena.to_host(a, b, codec=arena.CODEC_SZK)}
        mgr = CheckpointManager(tmp_path, async_save=False)
        mgr.save(1, state)
        out, _ = mgr.restore(state_like={"karena000": 0})
        got = out["karena000"]
        for k, v in raw.items():
            assert got[k].shape == v.shape and got[k].dtype == np.float32
            assert np.abs(got[k] - v).max() <= eb * (1 + 1e-5)

    def test_staged_flat_encode_matches_unstaged(self):
        rng = np.random.default_rng(9)
        named = [("a", rng.normal(size=(48, 32)).astype(np.float32)),
                 ("b", rng.normal(size=(96,)).astype(np.float32))]
        plan = arena.plan_for_tree(dict(named))
        for b in plan:
            leaves = [jnp.asarray(dict(named)[nm.strip("['']")])
                      for nm in b.names]
            a0 = arena.sz_compress_bucket(leaves, b, 1e-3)
            a1 = arena.sz_compress_bucket(leaves, b, 1e-3, staged=True)
            np.testing.assert_array_equal(
                np.asarray(a0.arena)[:int(a0.used)],
                np.asarray(a1.arena)[:int(a1.used)])
            np.testing.assert_array_equal(np.asarray(a0.widths),
                                          np.asarray(a1.widths))
            np.testing.assert_array_equal(np.asarray(a0.eb_i),
                                          np.asarray(a1.eb_i))
