"""The executors on the kernels' row view: all six, run on 4 forced CPU
devices with the slab kernels interpreted, are bitwise equal to the
``"xla"`` data plane, at a width whose row view is (N, 2, 128) and at one
whose row view is (N, 1, 96); and alltoallv, whose capacity buffer is
not zeroed and whose output the ``slab_unpack`` kernel builds unzeroed,
also on a skewed matrix whose padded slabs read past their sender's
input, with no NaN (an unwritten row) anywhere in its output and zeros
in every row past ``out_valid`` on both data planes.  One child process
runs every case."""
import os
import subprocess
import sys

import pytest

CHILD = os.path.join(os.path.dirname(__file__), "multidevice",
                     "child_dataplanes.py")
OPS = ("gatherv", "scatterv", "allgatherv", "alltoallv", "reduce_scatterv",
       "allreducev", "alltoallv_skew")


@pytest.fixture(scope="module")
def verdicts(child_env):
    env = dict(child_env, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    return {tuple(line.split()[1:3]): line.split()[3]
            for line in res.stdout.splitlines()
            if line.startswith("DATAPLANE ")}


@pytest.mark.parametrize("F", [256, 96])
@pytest.mark.parametrize("op", OPS)
def test_interpret_equals_xla_dataplane(op, F, verdicts):
    assert verdicts.get((op, str(F))) == "equal", verdicts
