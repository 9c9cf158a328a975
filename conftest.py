import atexit
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "src"))
sys.path.insert(0, os.path.dirname(__file__))


def pytest_sessionfinish(session, exitstatus):
    """Arrange to hard-exit with pytest's real status instead of running
    interpreter finalization.

    The jax CPU runtime can abort ("terminate called without an active
    exception", SIGABRT) during interpreter shutdown after a large suite
    — every test has passed and the summary printed when it fires, but
    the exit code becomes 134 and CI reads that as a failure. The atexit
    handler registers last, so it runs first: it flushes stdio and
    ``os._exit``s before the racy native teardown. The terminal summary
    still prints normally (sessionfinish returns)."""

    if "coverage" in sys.modules or os.environ.get("REPRO_NO_HARD_EXIT"):
        # os._exit would skip earlier-registered atexit hooks (coverage's
        # data-file save, profilers); let those runs take the SIGABRT
        # lottery instead of losing their data silently
        return

    def _exit_now(status=int(exitstatus)):
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(status)

    atexit.register(_exit_now)
