import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.state import AcceleratorState, GradientState, ProcessState


def test_process_state_singleton():
    a = ProcessState()
    b = ProcessState()
    assert a.__dict__ is b.__dict__
    assert a.initialized
    assert a.num_processes == 1
    assert a.is_main_process and a.is_last_process
    assert a.device_count == 8  # virtual CPU mesh from conftest


def test_wait_for_everyone_noop():
    ProcessState().wait_for_everyone()


def test_split_between_processes_single():
    state = ProcessState()
    with state.split_between_processes([1, 2, 3]) as chunk:
        assert chunk == [1, 2, 3]


def test_split_between_processes_math():
    # Simulate the index math directly for an 3-way split of 8 elements.
    state = ProcessState()
    state.__dict__["num_processes"] = 3
    items = list(range(8))
    chunks = []
    for rank in range(3):
        state.__dict__["process_index"] = rank
        with state.split_between_processes(items) as chunk:
            chunks.append(list(chunk))
    assert chunks == [[0, 1, 2], [3, 4, 5], [6, 7]]
    # Padding makes all chunks the same length by repeating the last element.
    state.__dict__["process_index"] = 2
    with state.split_between_processes(items, apply_padding=True) as chunk:
        assert list(chunk) == [6, 7, 7]
    # dict splitting
    state.__dict__["process_index"] = 0
    with state.split_between_processes({"a": [1, 2, 3, 4], "b": [5, 6, 7, 8]}) as d:
        assert d == {"a": [1, 2], "b": [5, 6]}
    # numpy splitting with padding
    state.__dict__["process_index"] = 2
    with state.split_between_processes(np.arange(8), apply_padding=True) as arr:
        np.testing.assert_array_equal(arr, [6, 7, 7])


def test_accelerator_state_mesh():
    state = AcceleratorState()
    mesh = state.mesh
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("data", "fsdp", "tensor", "sequence", "expert")
    assert state.num_processes == 1  # delegation to ProcessState


def test_gradient_state():
    gs = GradientState()
    assert gs.num_steps == 1
    assert gs.sync_gradients
    assert not gs.in_dataloader
    GradientState(gradient_accumulation_steps=4)
    assert gs.num_steps == 4  # singleton


def test_on_main_process_decorator():
    state = ProcessState()
    calls = []
    fn = state.on_main_process(lambda: calls.append(1))
    fn()
    assert calls == [1]


def test_rank_aware_tqdm():
    pytest.importorskip("tqdm")
    from accelerate_tpu.utils import tqdm

    bar = tqdm(range(3), desc="t")
    # single process == main process: bar enabled (close() flips disable,
    # so check before consuming)
    assert not bar.disable
    assert list(bar) == [0, 1, 2]


class TestRequireDecorators:
    """Capability gating (reference require_* pattern, testing.py:146-541)."""

    def test_multi_device_passes_on_sim_mesh(self):
        from accelerate_tpu.test_utils import require_multi_device

        @require_multi_device
        def probe():
            return True

        assert probe()  # conftest forces the 8-device CPU mesh

    def test_require_tpu_skips_on_cpu(self):
        import unittest

        from accelerate_tpu.test_utils import require_tpu

        @require_tpu
        def probe():
            return True

        with pytest.raises(unittest.SkipTest):
            probe()

    def test_require_devices_threshold(self):
        import unittest

        from accelerate_tpu.test_utils import require_devices

        @require_devices(8)
        def ok():
            return True

        assert ok()

        @require_devices(1000)
        def too_many():
            return True

        with pytest.raises(unittest.SkipTest):
            too_many()

    def test_slow_gated_by_env(self, monkeypatch):
        import unittest

        from accelerate_tpu.test_utils import slow

        monkeypatch.delenv("ATX_RUN_SLOW", raising=False)

        @slow
        def probe():
            return True

        with pytest.raises(unittest.SkipTest):
            probe()
        monkeypatch.setenv("ATX_RUN_SLOW", "1")

        @slow
        def probe2():
            return True

        assert probe2()

    def test_are_same_tensors(self):
        from accelerate_tpu.test_utils import are_same_tensors

        a = {"x": jnp.ones((2, 2)), "y": jnp.zeros(3)}
        b = {"x": jnp.ones((2, 2)), "y": jnp.zeros(3)}
        assert are_same_tensors(a, b)
        assert not are_same_tensors(a, {"x": jnp.ones((2, 2)), "y": jnp.ones(3)})
        assert not are_same_tensors(a, {"x": jnp.ones((2, 2))})


def test_require_decorator_on_plain_pytest_class():
    """Plain (non-TestCase) classes must carry a pytest skip mark."""
    from accelerate_tpu.test_utils import require_tpu

    @require_tpu
    class Probe:
        def test_x(self):
            pass

    marks = getattr(Probe, "pytestmark", [])
    assert any(m.name == "skipif" and m.args == (True,) for m in marks)


@pytest.mark.parametrize("placed", [None, "/some/dir"])
def test_configure_compile_cache(monkeypatch, placed):
    """JAX_COMPILATION_CACHE_DIR set: it is honoured and nothing is set in
    code. Unset: one fixed, git-ignored directory inside the checkout."""
    import os

    from accelerate_tpu import state

    before = jax.config.jax_compilation_cache_dir
    try:
        if placed is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert state.configure_compile_cache() == state.COMPILE_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == state.COMPILE_CACHE_DIR
            repo = os.path.dirname(os.path.dirname(os.path.abspath(state.__file__)))
            assert state.COMPILE_CACHE_DIR == os.path.join(repo, ".jax_compile_cache")
            with open(os.path.join(repo, ".gitignore")) as f:
                assert ".jax_compile_cache/" in f.read().split()
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
            assert state.configure_compile_cache() == placed
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
