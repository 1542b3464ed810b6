"""Test harness: simulate an 8-device TPU mesh on CPU.

The reference tests multi-process behavior by launching driver scripts under
`accelerate launch` on real multi-GPU runners (SURVEY.md §4). Here the primary
harness is JAX's host-platform device simulation: 8 virtual CPU devices let
every sharding/collective path run in plain single-process CI, which the
reference cannot do. Multi-process paths are additionally covered by
subprocess-launched driver scripts in `tests/scripts/`.
"""

import os
import sys

# No persistent compile cache under test (the flag is read at `import jax`):
# `commands.cli.main`, called in-process by the CLI tests, points JAX at the
# checkout's cache, which a fresh checkout would only pay to fill, and which
# cannot hold the described-TPU compiles of test_chip_compile / test_pod_aot.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

# ATX_TEST_REAL_CHIP=1 opts a run into the real accelerator (for the
# @require_tpu tests, e.g. host-offload placement); default is the
# deterministic 8-device CPU simulation.
if os.environ.get("ATX_TEST_REAL_CHIP"):
    import jax  # noqa: E402
else:
    # Must be set before jax initializes its backends.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
    # Force CPU even where a chip is attached: tests always run on the
    # virtual 8-device CPU mesh.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_singletons():
    """Fresh state singletons per test (reference `AccelerateTestCase`,
    `test_utils/testing.py:595-606`)."""
    from accelerate_tpu.state import AcceleratorState, GradientState, ProcessState

    yield
    AcceleratorState._reset_state()
    GradientState._reset_state()
    ProcessState._reset_state()


@pytest.fixture
def host_capture(tmp_path):
    """``host_capture(body)`` runs ``body`` inside a profiler capture started
    with the bare `jax.profiler.start_trace` — the way a benchmark or an
    operator's capture button starts one, with nothing told to the program —
    and returns the host plane's events in start order: name, start, end
    (nanoseconds on the capture's clock) and stats."""
    import glob
    import tempfile
    import warnings

    from jax.profiler import ProfileData

    def capture(body):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        log_dir = tempfile.mkdtemp(dir=tmp_path)
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        events = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in ProfileData.from_file(path).planes:
                if not plane.name.startswith("/host:"):
                    continue
                for line in plane.lines:
                    for e in line.events:
                        events.append({
                            "name": e.name, "start": e.start_ns, "end": e.start_ns + e.duration_ns,
                            "stats": {k: v for k, v in e.stats if not k.startswith("_")},
                        })
        return sorted(events, key=lambda e: (e["start"], -e["end"]))

    return capture


def pytest_addoption(parser):
    parser.addoption(
        "--heavy",
        action="store_true",
        default=False,
        help="Include tests marked 'heavy' (compile-heavy / subprocess "
        "launches). Default lane skips them so `pytest tests/` stays fast; "
        "`make test-all` runs everything.",
    )


def pytest_collection_modifyitems(config, items):
    """Split CI lanes (reference Makefile:25-60 pattern): the default
    `pytest tests/` run skips `heavy` tests; `--heavy` (or selecting them
    explicitly with `-m heavy`) includes them."""
    if config.getoption("--heavy") or config.getoption("-m"):
        return
    skip = pytest.mark.skip(reason="heavy lane: run with --heavy (or make test-all)")
    for item in items:
        if "heavy" in item.keywords:
            item.add_marker(skip)
