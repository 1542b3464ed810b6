"""Async chunked transfer engine (`parallel/transfer.py`) — the shared
H2D/D2H path for big-model load, over-RAM layer streaming, and offloaded
optimizer traffic.

All tests run on CPU with tiny arrays (chunk sizes forced down to exercise
the chunked path), so tier-1 covers the engine without TPU hardware — the
`-m 'not slow'` smoke lane (Makefile `smoke-transfer`). The invariants:
chunk reassembly is bit-exact, prefetch preserves order and depth,
exceptions from worker threads propagate to the caller, and staged layers
never alias each other (double-buffer reuse safety)."""

import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from accelerate_tpu import MeshConfig, build_mesh
from accelerate_tpu.big_modeling import streamed_scan
from accelerate_tpu.parallel.transfer import (
    TransferEngine,
    get_transfer_engine,
    overlap_enabled,
)


@pytest.fixture
def engine():
    # chunk_bytes=1024 forces multi-chunk reassembly on KiB-scale arrays.
    eng = TransferEngine(chunk_bytes=1024, workers=3, prefetch_depth=2)
    yield eng
    eng.close()


class TestPut:
    def test_chunked_reassembly_bit_exact(self, engine):
        x = np.random.RandomState(0).randn(257, 33).astype(np.float32)
        assert engine._should_chunk(x, None)
        d = engine.put(x).result()
        assert isinstance(d, jax.Array)
        np.testing.assert_array_equal(np.asarray(d), x)

    def test_single_shot_small_leaf(self, engine):
        x = np.arange(7, dtype=np.int32)
        assert not engine._should_chunk(x, None)
        np.testing.assert_array_equal(np.asarray(engine.put(x).result()), x)

    def test_scalar_and_zero_dim(self, engine):
        assert float(engine.put(np.float32(3.5)).result()) == 3.5
        z = engine.put(np.zeros((), np.int32)).result()
        assert z.shape == ()

    def test_dtype_cast_per_chunk(self, engine):
        x = np.random.RandomState(1).randn(300, 5).astype(np.float32)
        d = engine.put(x, dtype=jnp.bfloat16).result()
        assert d.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(d), x.astype(jnp.bfloat16))

    def test_memmap_source_reads_on_workers(self, engine, tmp_path):
        # The over-RAM disk-streaming case: chunk reads come straight off
        # the memmap on pool workers.
        x = np.random.RandomState(2).randn(128, 17).astype(np.float32)
        path = str(tmp_path / "leaf.bin")
        x.tofile(path)
        mm = np.memmap(path, mode="r", dtype=np.float32, shape=(128, 17))
        np.testing.assert_array_equal(np.asarray(engine.put(mm).result()), x)

    def test_odd_row_remainder(self, engine):
        # shape[0] not divisible by the chunk row count: the tail chunk is
        # smaller and must still land exactly.
        x = np.arange(101 * 13, dtype=np.float32).reshape(101, 13)
        np.testing.assert_array_equal(np.asarray(engine.put(x).result()), x)

    def test_jax_array_input_reshards(self, engine):
        x = jnp.arange(64.0).reshape(8, 8)
        d = engine.put(x).result()
        np.testing.assert_array_equal(np.asarray(d), np.asarray(x))

    def test_worker_exception_propagates(self, engine):
        class Boom:
            pass

        with pytest.raises(TypeError):
            engine.put(Boom()).result()

    def test_submit_exception_propagates(self, engine):
        def boom():
            raise RuntimeError("worker boom")

        with pytest.raises(RuntimeError, match="worker boom"):
            engine.submit(boom).result()


class TestShardedPut:
    def test_dim1_sharded_leaf_chunks(self, engine):
        mesh = build_mesh(MeshConfig(data=2, fsdp=4))
        sh = NamedSharding(mesh, PartitionSpec(None, ("data", "fsdp")))
        x = np.random.RandomState(3).randn(64, 64).astype(np.float32)
        assert engine._should_chunk(x, sh)
        d = engine.put(x, sh).result()
        assert d.sharding == sh
        np.testing.assert_array_equal(np.asarray(d), x)

    def test_dim0_sharded_leaf_single_shot(self, engine):
        mesh = build_mesh(MeshConfig(data=2, fsdp=4))
        sh = NamedSharding(mesh, PartitionSpec("data", None))
        x = np.random.RandomState(4).randn(64, 64).astype(np.float32)
        # Row chunking cannot satisfy a dim-0-partitioned layout; the leaf
        # must fall back to one placement call — and still be correct.
        assert not engine._should_chunk(x, sh)
        d = engine.put(x, sh).result()
        assert d.sharding == sh
        np.testing.assert_array_equal(np.asarray(d), x)

    def test_replicated_sharding_chunks(self, engine):
        mesh = build_mesh(MeshConfig())
        sh = NamedSharding(mesh, PartitionSpec())
        x = np.random.RandomState(5).randn(96, 16).astype(np.float32)
        assert engine._should_chunk(x, sh)
        d = engine.put(x, sh).result()
        np.testing.assert_array_equal(np.asarray(d), x)


class TestTrees:
    def test_put_tree_mixed_shardings(self, engine):
        mesh = build_mesh(MeshConfig(data=2, fsdp=4))
        tree = {
            "big": np.random.RandomState(6).randn(128, 9).astype(np.float32),
            "small": np.arange(3, dtype=np.int32),
        }
        shardings = {
            "big": NamedSharding(mesh, PartitionSpec()),
            "small": None,
        }
        out = engine.put_tree(tree, shardings).result()
        np.testing.assert_array_equal(np.asarray(out["big"]), tree["big"])
        np.testing.assert_array_equal(np.asarray(out["small"]), tree["small"])

    def test_put_tree_single_sharding_broadcasts(self, engine):
        mesh = build_mesh(MeshConfig())
        sh = NamedSharding(mesh, PartitionSpec())
        tree = [np.ones((4, 4), np.float32), np.zeros((2,), np.float32)]
        out = engine.put_tree(tree, sh).result()
        assert all(o.sharding == sh for o in out)

    def test_put_tree_structure_mismatch_raises(self, engine):
        mesh = build_mesh(MeshConfig())
        sh = NamedSharding(mesh, PartitionSpec())
        with pytest.raises(ValueError, match="leaves"):
            engine.put_tree({"a": np.ones(2), "b": np.ones(2)}, [sh])

    def test_get_tree_roundtrip(self, engine):
        tree = {"w": np.random.RandomState(7).randn(40, 3).astype(np.float32)}
        dev = engine.put_tree(tree).result()
        host = engine.get_tree(dev).result()
        assert isinstance(host["w"], np.ndarray)
        np.testing.assert_array_equal(host["w"], tree["w"])


class TestPrefetch:
    def test_yields_in_order_with_depth_ahead(self, engine):
        submitted = []

        def stage(i):
            submitted.append(i)
            return engine.put(np.full((300, 5), i, np.float32))

        seen = []
        for i, layer in enumerate(engine.prefetch(6, stage, depth=2)):
            assert float(np.asarray(layer)[0, 0]) == i
            # While consuming item i, stages up to i+depth were submitted.
            assert max(submitted) >= min(i + 2, 5)
            seen.append(i)
        assert seen == list(range(6))
        assert submitted == list(range(6))  # each stage called exactly once

    def test_plain_values_pass_through(self, engine):
        assert list(engine.prefetch(4, lambda i: i * 10)) == [0, 10, 20, 30]

    def test_stage_exception_raises_at_yield(self, engine):
        def stage(i):
            if i == 2:
                return engine.submit(lambda: (_ for _ in ()).throw(
                    RuntimeError("stage 2 boom")
                ))
            return engine.put(np.zeros((4,), np.float32))

        it = engine.prefetch(4, stage, depth=2)
        next(it)
        next(it)
        with pytest.raises(RuntimeError, match="stage 2 boom"):
            next(it)

    def test_double_buffer_reuse_safety(self, engine):
        """Consuming layer i while i+1..i+depth are in flight must never
        alias or clobber a previously yielded layer's device buffer."""
        host = np.stack([np.full((64, 7), i, np.float32) for i in range(8)])

        def stage(i):
            return engine.put(host[i])

        kept = list(engine.prefetch(8, stage, depth=3))
        for i, layer in enumerate(kept):  # all retained layers still correct
            np.testing.assert_array_equal(
                np.asarray(layer), np.full((64, 7), i, np.float32)
            )


class TestStreamedScan:
    def test_matches_direct_loop(self, engine):
        blocks = {
            "w": np.random.RandomState(8).randn(5, 33, 3).astype(np.float32),
            "b": np.random.RandomState(9).randn(5, 3).astype(np.float32),
        }
        carry = jnp.zeros((3,), jnp.float32)

        def body(c, blk):
            return c + jnp.sum(blk["w"], axis=0) + blk["b"]

        got = streamed_scan(body, carry, blocks, engine=engine)
        want = np.zeros((3,), np.float32)
        for i in range(5):
            want = want + blocks["w"][i].sum(axis=0) + blocks["b"][i]
        # fp32 reduction-order noise only (device sum vs numpy sum).
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

    def test_dtype_cast_and_depth(self, engine):
        blocks = {"w": np.random.RandomState(10).randn(4, 300, 2).astype(np.float32)}
        seen_dtypes = []

        def body(c, blk):
            seen_dtypes.append(blk["w"].dtype)
            return c + 1

        out = streamed_scan(
            body, 0, blocks, dtype=jnp.bfloat16, engine=engine, prefetch_depth=3
        )
        assert out == 4
        assert all(d == jnp.bfloat16 for d in seen_dtypes)


class TestKnobs:
    def test_env_knobs_read_at_construction(self, monkeypatch):
        monkeypatch.setenv("ATX_TRANSFER_CHUNK_MIB", "2")
        monkeypatch.setenv("ATX_TRANSFER_WORKERS", "7")
        monkeypatch.setenv("ATX_TRANSFER_PREFETCH", "5")
        eng = TransferEngine()
        try:
            assert eng.chunk_bytes == 2 << 20
            assert eng.workers == 7
            assert eng.prefetch_depth == 5
        finally:
            eng.close()

    def test_garbage_env_falls_back_to_defaults(self, monkeypatch):
        monkeypatch.setenv("ATX_TRANSFER_CHUNK_MIB", "not-a-number")
        eng = TransferEngine()
        try:
            assert eng.chunk_bytes == 64 << 20
        finally:
            eng.close()

    def test_overlap_enabled_default_and_opt_out(self, monkeypatch):
        monkeypatch.delenv("ATX_OFFLOAD_OVERLAP", raising=False)
        assert overlap_enabled()
        for off in ("0", "false", "off", "no"):
            monkeypatch.setenv("ATX_OFFLOAD_OVERLAP", off)
            assert not overlap_enabled()
        monkeypatch.setenv("ATX_OFFLOAD_OVERLAP", "1")
        assert overlap_enabled()

    def test_singleton(self):
        assert get_transfer_engine() is get_transfer_engine()


class TestCachePythonIntStart:
    """Regression (`models/layers.py`): caches built with plain Python int
    lengths were previously valid, then `start.ndim` started raising
    AttributeError — `cache_positions`/`cache_write` normalize now."""

    def test_cache_positions_accepts_python_int(self):
        from accelerate_tpu.models.layers import cache_positions

        pos = cache_positions(3, 4, 2)
        np.testing.assert_array_equal(
            np.asarray(pos), np.broadcast_to(np.arange(3, 7), (2, 4))
        )

    def test_cache_write_accepts_python_int(self):
        from accelerate_tpu.models.layers import cache_write

        buf = jnp.zeros((2, 8, 4), jnp.float32)
        new = jnp.ones((2, 2, 4), jnp.float32)
        out = cache_write(buf, new, 3)
        np.testing.assert_array_equal(
            np.asarray(out[:, 3:5]), np.ones((2, 2, 4), np.float32)
        )
        assert float(jnp.sum(out)) == pytest.approx(16.0)

    def test_cache_write_stacked_accepts_python_int(self):
        from accelerate_tpu.models.layers import cache_write_stacked

        all_buf = jnp.zeros((3, 2, 8, 4), jnp.float32)
        rows = jnp.ones((2, 2, 4), jnp.float32)
        stacked = cache_write_stacked(all_buf, jnp.int32(1), rows, 2)
        np.testing.assert_array_equal(
            np.asarray(stacked[1, :, 2:4]), np.ones((2, 2, 4), np.float32)
        )
        assert float(jnp.sum(stacked)) == pytest.approx(16.0)

    def test_cache_write_stacked_scatters_rows_at_per_row_cursors(self):
        """A (B,) cursor writes row b's T new rows at its own offset, into
        the named layer only; a row that would land past the end of the
        buffer is dropped, not clamped onto a committed position."""
        from accelerate_tpu.models.layers import cache_write_stacked

        all_buf = jnp.zeros((3, 3, 8, 4), jnp.float32)
        rows = jnp.arange(1, 7, dtype=jnp.float32).reshape(3, 2, 1) * jnp.ones((3, 2, 4))
        out = np.asarray(
            jax.jit(cache_write_stacked)(all_buf, jnp.int32(2), rows, jnp.asarray([0, 5, 7]))
        )
        want = np.zeros((3, 8, 4), np.float32)
        want[0, 0], want[0, 1] = 1.0, 2.0
        want[1, 5], want[1, 6] = 3.0, 4.0
        want[2, 7] = 5.0  # its second row (6.0) falls off the end
        np.testing.assert_array_equal(out[2], want)
        assert not out[:2].any()
