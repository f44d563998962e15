"""Driver-contract test: run ``__graft_entry__.dryrun_multichip`` exactly
the way the driver does -- a fresh interpreter whose environment does NOT
preselect a JAX platform -- and require it to pass hermetically.

This is the regression test for an early failure: the dryrun
initialized the default backend (whatever accelerator the caller had)
before falling back to CPU devices.  The wrapper now
re-execs its body in a scrubbed CPU-only env, so this must pass no matter
what backend the calling process would default to.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_driver_contract():
    env = dict(os.environ)
    # Simulate the driver's raw environment: no explicit platform choice,
    # whatever XLA_FLAGS happen to be set (the wrapper must override the
    # virtual device count itself).
    env.pop("JAX_PLATFORMS", None)
    code = ("import sys; sys.path.insert(0, %r); "
            "import __graft_entry__ as g; g.dryrun_multichip(8)" % REPO)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:]
    assert "dryrun_multichip OK" in proc.stdout, proc.stdout[-4000:]
