"""Shared pytest hooks.

When a shard runs under ``WILKINS_LOCKCHECK=1`` (see ``repro.analysis.
lockcheck``) every lock the core constructs is a checked wrapper recording
the cross-thread acquisition graph.  At session end we fail the run if the
recorder saw a lock-order cycle, a rank inversion, or a blocking call under
a fine-grained lock -- even if every individual test passed.

The tests run on the CPU backend (Pallas kernels in interpret mode), and so
do the processes they start: on a machine with a TPU they never contend
for the chip.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def pytest_sessionfinish(session, exitstatus):
    if os.environ.get("WILKINS_LOCKCHECK", "") in ("", "0"):
        return
    from repro.analysis.lockcheck import registry
    findings = registry().findings()
    if findings.errors():
        print("\nWILKINS_LOCKCHECK: lock-discipline violations recorded:",
              file=sys.stderr)
        print(findings.render_text(), file=sys.stderr)
        session.exitstatus = 1
