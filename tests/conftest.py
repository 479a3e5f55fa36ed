import os

# Lock-order witness (ISSUE 15): armed for the whole tier-1 run BEFORE
# any seaweedfs_tpu import — the utils/locks.py factories read the gate
# at construction time, so this line is what turns every chaos/dispatch/
# group-commit/pool scenario into a deadlock detector. Production keeps
# the default (off ⇒ the factories return plain threading primitives).
os.environ.setdefault("SWFS_LOCK_WITNESS", "1")

# Tests run on a virtual 8-device CPU mesh; the chip is for chip_smoke.py
# and benchmark/run.py. The CPU pin goes through jax.config before any backend is
# instantiated, so it holds whatever JAX_PLATFORMS says — and it is the
# explicit pin the "tpu" coder accepts in place of a TPU
# (models/coder.require_tpu).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-GB EC scale tests (deselect with -m 'not slow')"
    )
    config.addinivalue_line(
        "markers",
        "chaos: failpoint-driven fault-injection suite (tests/test_chaos.py);"
        " runs inside the tier-1 -m 'not slow' selection"
    )


import faulthandler  # noqa: E402
import pytest  # noqa: E402

# Per-test watchdog: if any single test hangs for 5 minutes (the slowest
# legitimate test is ~70s), dump every thread's stack and kill the run —
# a diagnosable failure beats an infinitely hung CI/driver session.
_WATCHDOG_SECONDS = 300


@pytest.fixture(autouse=True)
def _lock_witness_guard():
    """Fail the test that (first) observed a lock-order violation. The
    witness records instead of raising (a daemon thread's raise would
    be swallowed), so this guard is what turns a recorded inversion
    into a red run.

    Deliberately NO locks.reset() between tests: lock order is a
    program-wide invariant (FreeBSD witness accumulates for the system
    lifetime), so an A->B established by one test legitimately
    convicts a B->A in a later one — that cross-test pairing is most
    of the detector's power. The cost is attribution: the failing test
    may only be the OBSERVER of an inversion another test's surviving
    daemon thread completed; the violation detail (lock names, thread
    names, first-seen site) is what localizes it."""
    from seaweedfs_tpu.utils import locks

    if not locks.witness_enabled():
        yield
        return
    before = len(locks.violations())
    yield
    after = locks.violations()
    assert len(after) <= before, (
        "lock-order witness recorded violations during this test "
        "(cross-thread acquisition-order inversion or rank breach):\n"
        + "\n".join(repr(v) for v in after[before:]))


@pytest.fixture(autouse=True)
def _hang_watchdog(request):
    # multi-GB "slow" tests get a far wider budget on loaded machines
    budget = 900 if request.node.get_closest_marker("slow") \
        else _WATCHDOG_SECONDS
    faulthandler.dump_traceback_later(budget, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()


# -- duration audit (ISSUE 3 satellite): every test that takes longer than
# the threshold MUST carry @pytest.mark.slow, or the run fails. PR 1 shipped
# a 252s mesh test into tier-1 unmarked and silently ate a third of the
# tier-1 budget for a round; this makes that class of regression loud.
_SLOW_AUDIT_THRESHOLD = float(os.environ.get("SWFS_TEST_SLOW_THRESHOLD",
                                             "120"))
_overlong: list[tuple[str, float]] = []


def pytest_runtest_logreport(report):
    if report.when != "call" or report.duration <= _SLOW_AUDIT_THRESHOLD:
        return
    if "slow" in getattr(report, "keywords", {}):
        return
    _overlong.append((report.nodeid, report.duration))


def pytest_terminal_summary(terminalreporter):
    if not _overlong:
        return
    terminalreporter.section("slow-test audit FAILED")
    for nodeid, dur in _overlong:
        terminalreporter.write_line(
            f"  {nodeid} took {dur:.1f}s (> {_SLOW_AUDIT_THRESHOLD:.0f}s) "
            f"without @pytest.mark.slow")
    terminalreporter.write_line(
        "  mark these slow (or speed them up) — unmarked long tests eat "
        "the tier-1 budget for every future run")


def pytest_sessionfinish(session, exitstatus):
    # flip a green run red when the audit tripped; pytest returns
    # session.exitstatus AFTER this hook, so the mutation sticks
    if _overlong and exitstatus == 0:
        session.exitstatus = 1
